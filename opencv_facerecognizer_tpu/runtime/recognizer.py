"""Recognizer service: the reference's recognizer node rebuilt
(SURVEY.md §3.3: "enqueue frame -> batcher -> one sharded
detect->align->embed->match call per batch").

Flow: connector frames -> FrameBatcher (continuous batching) ->
RecognitionPipeline (one fused device call per batch, sliced to a bucket
of the dispatch ladder) -> in-flight queue -> **readback worker** -> result
messages on the connector.

Three hard-won design points (see parallel/gallery.py for a sibling
finding):

- **The serving loop never waits on device results.** Dispatch is
  asynchronous, so a loop that blocked on each batch's readback would
  leave the device idle while the host publishes and batches. The
  service therefore dispatches a batch, calls ``copy_to_host_async`` on
  the output, parks it in the in-flight queue, and a dedicated **readback
  worker thread** blocks on each batch's device array (event-driven
  ``block_until_ready``, via a sacrificial blocker thread so the wait
  stays bounded by the per-batch deadline) and runs the publish path.
  Dispatch, D2H, and publish overlap; ``inflight_depth`` slots actually
  pipeline. What the overlap buys on the locally attached chip: not
  measured.
- **Feed the chip, then settle.** The one readback the loop does make
  is the stage-1 gate's scores, and everything the loop does between a
  step's end and the next step's enqueue is time the chip sits out. So
  ``_serve_one`` first does what the chip waits on (scores read,
  survivors compacted, upload, step enqueue) and only then publishes the
  batch's early exits; and when a closed batch is already waiting (the
  tracker was not consulted for this one, and the loop waits for the
  chip, not the chip for the loop), that batch's gate goes onto the
  device's queue AHEAD of this batch's step, so its scores come back
  while the step runs and its step is queued behind. The loop never
  waits for frames to look ahead.
- **Bucketed dispatch cache**: a partial batch is sliced down to the
  smallest size in a fixed ``bucket_sizes`` ladder (default 8/32/128,
  filtered to the mesh's dp divisibility and capped at ``batch_size``)
  instead of always padding to the full batch. Every ladder size is
  compiled at ``warmup()``, so partial batches never trigger recompiles,
  and the staging array each batch rides in is recycled back to the
  batcher's buffer pool once its readback completes (the host-side analog
  of a donated input buffer: steady-state dispatch does zero per-batch
  allocations. True XLA buffer donation does not apply here — the inputs
  are host numpy arrays, which jit copies rather than aliases).
- **Reload without drop** (SURVEY.md §5.3): retraining builds a NEW gallery
  (or pipeline) off-thread; ``reload_gallery`` swaps the reference between
  batches. In-flight batches keep the arrays they captured.

The interactive-trainer protocol (SURVEY.md §2.1 "Interactive trainer")
rides the same connector: an ``enroll`` command captures the next N detected
face crops for a subject, embeds them, and installs the grown gallery.

Steady-state failure handling (the round-4 outage, generalized — see
``runtime.resilience``): a dispatch failure retries with exponential
backoff (transient/outage-shaped errors only; a poisoned batch is abandoned
immediately), a readback that outlives its per-batch deadline is
dead-lettered **by the readback worker** and the loop keeps serving, and N
consecutive dispatch failures flip the service into degraded mode with a
``STATUS_TOPIC`` announcement (optionally probing the device it holds via
``resilience.probe_device`` and invoking a CPU-fallback hook when it is
dead).
A crash that escapes either serving-side thread (the dispatch loop or the
readback worker) sets ``loop_crashed`` for ``resilience.ServiceSupervisor``
to restart with the last-known-good gallery; each crash path settles its
own batch accounting first, so ``drain()`` stays solvable after a restart.
``runtime.faults.FaultInjector`` installs at every one of these boundaries
to make the whole story testable.

**Overload protection** (the client-side mirror of the resilience story —
nothing above protects the loop from its own producers):

- **Admission control** (``runtime.admission``): ``_on_frame`` consults an
  optional ``AdmissionController`` BEFORE decoding — a rate-limited or
  over-bound frame is rejected explicitly (``frames_rejected_<reason>``
  plus an aggregated ``rejected`` backpressure status on ``STATUS_TOPIC``)
  instead of silently displacing someone else's frame later. Frames carry
  an optional ``priority`` ("interactive" default / "bulk"); the batcher
  sheds stale and low-priority frames first under pressure, and drops
  anything older than ``shed_stale_after_s`` before it can waste a
  dispatch slot.
- **Brownout controller**: a queue-wait EWMA crossing
  ``BrownoutPolicy.queue_wait_s`` degrades work per frame with hysteresis
  — level 1 skip-k sheds bulk intake, level 2 sheds all bulk and caps the
  dispatch bucket ladder at its smallest rung — announced on the status
  topic with a ``brownout_level`` gauge, recovering automatically.
- **Admission ledger**: every admitted frame ends in exactly one bucket —
  ``admitted == completed + Σ drops_by_reason`` (``ledger()``); shed /
  dead-lettered / abandoned frames also append metadata + reason to the
  optional durable ``DeadLetterJournal`` so producers can retry.

**Durable state** (``runtime.state_store``, wired via ``state_store=``):
an enrolment write-ahead-logs its embeddings/labels (fsynced per policy)
before the gallery mutation and is acknowledged only after — restart
recovery (checkpoint + WAL replay) then loses nothing acknowledged. The
serving loop ticks the lifecycle's checkpoint thresholds each iteration;
the checkpoint itself (host-mirror ``snapshot()`` + atomic checksummed
write) runs on a background thread behind a single-flight guard, so
dispatch never blocks on durability. ``reload_gallery`` forces a
checkpoint — a swap is not WAL-representable.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils import native
from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
from opencv_facerecognizer_tpu.runtime.admission import (
    PRIORITY_INTERACTIVE,
    AdmissionController,
    parse_priority,
)
from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher
from opencv_facerecognizer_tpu.runtime.connector import (
    MiddlewareConnector,
    decode_frame_counted,
)
from opencv_facerecognizer_tpu.runtime.ingest import (
    JPEG_KEY,
    IngestConfig,
    IngestPipeline,
)
from opencv_facerecognizer_tpu.runtime.resilience import (
    BrownoutPolicy,
    DurabilityDegradedError,
    ResiliencePolicy,
    is_transient_error,
    probe_device,
)
from opencv_facerecognizer_tpu.runtime.slo import STATE_CRITICAL
from opencv_facerecognizer_tpu.utils.metrics import Metrics
from opencv_facerecognizer_tpu.utils import tracing

FRAME_TOPIC = "ocvfacerec/frames"
RESULT_TOPIC = "ocvfacerec/results"
CONTROL_TOPIC = "ocvfacerec/control"
STATUS_TOPIC = "ocvfacerec/status"
#: link-supervision heartbeats (ISSUE 16): the router pings each replica
#: on ``ping``; the service echoes the payload back on ``pong``.  An
#: application-level round trip proves the whole path — connector, wire,
#: dispatch thread — where TCP liveness proves only the kernel's half.
LINK_PING_TOPIC = "ocvfacerec/link/ping"
LINK_PONG_TOPIC = "ocvfacerec/link/pong"

#: ``_await_ready``'s bounded ``is_ready`` poll interval, for a readback
#: that refuses to block (a proxy whose ``block_until_ready`` raises, such
#: as an injected stuck readback). The worker never polls a healthy
#: readback: it blocks on the array.
READY_POLL_S = 0.005
#: Liveness tick of the condition waits: ``drain()``'s re-check interval,
#: and the upper bound between re-checks of ``_running`` / the crash flag
#: in the loop's slot wait and the worker's work wait. Each of them is
#: woken by ``notify_all`` when there is something to see.
LIVENESS_TICK_S = 0.05
#: Dispatch bucket ladder (capped at ``batch_size``, filtered to the mesh's
#: dp divisibility): a partial batch is sliced to the smallest bucket >= its
#: real frame count, so light traffic pays small-batch compute without ever
#: compiling a new shape mid-serving.
DEFAULT_BUCKET_SIZES = (8, 32, 128)
#: Default stage-1 cascade operating point (mirrors
#: ``models.cascade.DEFAULT_THRESHOLD`` without importing flax here):
#: frames scoring below it are face-free early exits (``completed_empty``),
#: frames at/above it survive to the full detector.
DEFAULT_CASCADE_THRESHOLD = 0.3
#: How much ``--cascade-threshold`` tightens per brownout escalation: at
#: effective brownout level >= 1 the gate raises its threshold one notch
#: (rejecting MORE borderline frames — shedding device work) BEFORE the
#: intake skip starts dropping admitted bulk frames outright.
CASCADE_BROWNOUT_NOTCH = 0.15


@dataclass
class _Enrolment:
    subject_name: str
    needed: int
    crops: List[np.ndarray] = field(default_factory=list)


class _ReadbackBlocker:
    """One daemon helper thread that performs the potentially-unbounded
    ``block_until_ready`` so the readback worker's wait on a batch can be
    bounded by that batch's deadline. ``block`` returns ``"ready"`` (the
    array's transfer completed), ``"raised"`` (blocking raised — an
    injected never-ready proxy, or a failed computation), or ``"timeout"``
    (deadline passed while still blocked). After a timeout the helper may
    be wedged in native code — the hang-mode outage — so the caller must
    abandon this instance and build a fresh one; the abandoned daemon
    thread parks forever on its own (now unreachable) condition variable.
    """

    def __init__(self):
        self._cv = threading.Condition()
        self._pending: Any = None
        self._done = threading.Event()
        self._ok = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="ocvf-readback-blocker")
        self._thread.start()

    def _run(self) -> None:
        while True:
            with self._cv:
                while self._pending is None:
                    self._cv.wait()
                arr = self._pending
            try:
                arr.block_until_ready()  # ocvf-lint: boundary=host-sync -- THE designed readback wait: this sacrificial blocker thread exists so the worker's wait stays deadline-bounded; the serving loop itself never blocks
                self._ok = True
            except Exception:  # ocvf-lint: disable=swallowed-exception -- failure IS recorded: _ok=False is read by block(), whose caller classifies the outage and dead-letters the batch
                self._ok = False
            with self._cv:
                self._pending = None
            self._done.set()

    def block(self, arr: Any, timeout: float) -> str:
        self._done.clear()
        with self._cv:
            self._pending = arr
            self._cv.notify()
        if not self._done.wait(timeout=max(0.0, timeout)):
            return "timeout"
        return "ready" if self._ok else "raised"


#: The loop puts the next batch's gate ahead of a step only while it
#: waits for the chip (``gate_wait``) for more than this share of its
#: iterations' wall time, both smoothed over about twenty iterations
#: (``CHIP_BOUND_ALPHA``): the chip is then the slower party and still
#: busy when the loop comes to enqueue. Below it the host (or the source
#: of frames) sets the pace and looking ahead only reorders host work: on
#: one v5e chip it cost 4 % of ``served_fps`` where the share is 0.47
#: (``watchlist8m.crowd``) and gained 7 % where it is 0.89
#: (``watchlist4m-r50.crowd``), and a verdict taken from single
#: iterations flips every third one and loses in both (PERF.md section 6,
#: PR 30).
CHIP_BOUND_SHARE = 2.0 / 3.0
CHIP_BOUND_ALPHA = 0.05

#: Admitted frames between two reads of the handler thread's CPU clock
#: (``intake_thread_cpu_s``): a batch's worth, so the handler pays the
#: system call as often as the loop and the worker pay theirs.
INTAKE_CPU_EVERY = 128


class _Leaf:
    """One leaf of the serving loop's time (README "Observability", the
    table of leaves): the block's seconds are added to ``busy[stage]``,
    which the loop flushes as the always-on counter ``loop_s_<stage>``
    once per batch, and ``span`` (the tracer's, or ``NULL_SPAN`` for an
    untraced batch) is entered around the same block."""

    __slots__ = ("_busy", "_stage", "_span", "_t0")

    def __init__(self, busy: Dict[str, float], stage: str, span):
        self._busy = busy
        self._stage = stage
        self._span = span

    def __enter__(self):
        self._t0 = time.monotonic()
        return self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._busy[self._stage] = (self._busy.get(self._stage, 0.0)
                                   + time.monotonic() - self._t0)
        return False


class _Held:
    """One popped batch in the serving loop's hands, from its pop to its
    step's enqueue: the batch as the early exits have compacted it so far
    (``batch.count`` real frames at the staging buffer's front), the spans
    it rides (``batch_tid``, the id of its open ``dispatch`` span, the
    instant that span began), and the early exits gathered and not yet
    published: ``cached`` rows for ``_complete_cached``, ``rejected`` rows
    for ``_complete_empty``. ``tracked``: some frame of the batch names a
    stream, so the tracker was consulted for it and is owed the batch's
    misses before it is consulted for the next."""

    __slots__ = ("batch", "batch_tid", "disp_id", "t0", "cached", "rejected",
                 "tracked")

    def __init__(self, batch, batch_tid: int, disp_id: int, t0: float):
        self.batch = batch
        self.batch_tid = batch_tid
        self.disp_id = disp_id
        self.t0 = t0
        self.cached: List[tuple] = []
        self.rejected: List[tuple] = []
        self.tracked = False


class RecognizerService:
    def __init__(
        self,
        pipeline: RecognitionPipeline,
        connector: MiddlewareConnector,
        batch_size: int = 8,
        frame_shape: Optional[tuple] = None,
        flush_timeout: float = 0.05,
        # Backpressure knob: beyond this many undrained batches the dispatch
        # loop waits for the readback worker to free a slot before popping
        # more. Keep it shallow — each in-flight batch is a full device
        # round-trip of latency debt; a deep queue turns into backlog
        # while the batcher keeps accepting frames.
        inflight_depth: int = 4,
        similarity_threshold: float = 0.3,
        subject_names: Optional[List[str]] = None,
        metrics: Optional[Metrics] = None,
        # uint8 ships frames host->device 4x cheaper (cast to f32 happens
        # in-graph); right whenever the source is 8-bit camera frames.
        transfer_dtype=np.float32,
        # Steady-state failure handling (runtime.resilience docstring).
        resilience: Optional[ResiliencePolicy] = None,
        # Chaos hook (runtime.faults): installs at connector receive,
        # batcher put, device dispatch, and async readback. None in
        # production — every hook site is a no-op without it.
        fault_injector=None,
        # Degraded-mode backend check, injectable for tests. Default is
        # resilience.probe_device: a deadline-bounded tiny op on the device
        # this service's gallery already lives on, in this process.
        backend_probe_fn: Optional[Callable[[], tuple]] = None,
        # Called with this service when degraded mode finds the backend
        # dead: the app wires its CPU re-initialization here (rebuild the
        # pipeline on host devices) so a dead accelerator degrades the
        # job instead of wedging it.
        cpu_fallback: Optional[Callable[["RecognizerService"], None]] = None,
        # Dispatch bucket ladder (None/() disables slicing: every batch
        # dispatches at the full padded batch_size, the old behavior).
        bucket_sizes: Optional[Sequence[int]] = DEFAULT_BUCKET_SIZES,
        # Continuous-batching latency target, forwarded to the batcher's
        # adaptive flush deadline (None keeps the fixed flush_timeout).
        target_latency_s: Optional[float] = None,
        # ---- overload protection (module docstring) ----
        # Front-door admission control: rate limits + bounded intake,
        # consulted per frame BEFORE decode. None = admit everything.
        admission: Optional[AdmissionController] = None,
        # Brownout degradation knobs. None disables the controller.
        brownout: Optional[BrownoutPolicy] = None,
        # Durable dead-letter journal (runtime.journal.DeadLetterJournal):
        # shed/dead-lettered/abandoned frames append metadata + reason
        # here. None keeps counter-only accounting.
        dead_letter_journal=None,
        # Freshness bound forwarded to the batcher: queued frames older
        # than this are shed (reason ``stale``) rather than dispatched.
        shed_stale_after_s: Optional[float] = None,
        # Crash-safe state lifecycle (runtime.state_store.StateLifecycle):
        # enrollments write-ahead to its WAL before touching the gallery,
        # the serving loop ticks its checkpoint thresholds, and a reload
        # forces a durable checkpoint. None keeps state memory-only (the
        # pre-durability behavior).
        state_store=None,
        # Frame-lifecycle tracer (utils.tracing.Tracer): per-frame causal
        # spans (receive -> queue_wait -> settle), per-batch spans
        # (dispatch/ready_wait/publish with coalescing ancestry), brownout
        # lifecycle spans, and the flight-recorder dump on dead-letter.
        # None = tracing fully off (zero overhead).
        tracer=None,
        # SLO burn-rate monitor (runtime.slo.SLOMonitor): ticked by the
        # serving loop (evaluation every interval_s); its health verdict
        # feeds /health, the recompile watchdog's warn events, and — at
        # critical — one extra level of brownout intake pressure. None =
        # no SLO evaluation (zero overhead).
        slo_monitor=None,
        # Read-replica role (runtime.replication.ReadReplica): the serving
        # loop polls the shared WAL between batches and applies new
        # enrollment rows through the same gallery.add route replay uses.
        # A service with a replica is read-only for enrollment — enroll
        # commands are rejected with an explicit status (the writer lease
        # in the shared state dir owns the write path). None = this
        # process owns its own state (the pre-replication behavior).
        replica=None,
        # Ingest subsystem config (runtime.ingest.IngestConfig): installs
        # the pre-allocated staging ring in place of the ad-hoc buffer
        # pool, picks the transfer dtype from its mode (overriding
        # ``transfer_dtype``), routes dispatches through the explicit
        # device uploader, and (jpeg mode) runs the off-thread decode
        # worker pool for compressed camera payloads. None = the
        # pre-ingest behavior, unchanged.
        ingest: Optional[IngestConfig] = None,
        # ---- cascade early-exit detection (ISSUE 13) ----
        # Master switch for the two-stage gate (the --no-cascade escape
        # hatch). Active only when the pipeline also carries a stage-1
        # model (``pipeline.cascade`` + ``cascade_scores``); True with a
        # cascade-less pipeline is the unchanged single-stage behavior.
        cascade: bool = True,
        # Stage-1 operating point: frames scoring below it settle as
        # ``completed_empty`` without ever reaching the full detector.
        # None adopts the gate's own trained threshold (or the default).
        cascade_threshold: Optional[float] = None,
        # Brownout integration: threshold tightening per escalation (the
        # cheapest shed — reject borderline frames at stage 1 before the
        # intake skip drops admitted frames outright). 0 disables.
        cascade_brownout_notch: float = CASCADE_BROWNOUT_NOTCH,
        # ---- temporal identity cache (ISSUE 17) ----
        # An IdentityTracker (runtime.tracker) or None. When set, frames
        # whose ``meta["stream"]`` has live confirmed tracks — all inside
        # their re-verify window, appearance-stable and embedder-version
        # matched — settle as ``completed_cached`` with the cached
        # identities BEFORE the cascade gate (a tracker lookup is pure
        # host work, cheaper than the stage-1 device pass); every full
        # published result feeds back through ``tracker.update``. None =
        # every frame takes the full path (the --no-track-cache hatch).
        tracker=None,
        # ---- idempotent intake (ISSUE 16) ----
        # Frame-id dedup window: a delivery whose ``meta["_fid"]`` was
        # already ADMITTED is refused before admission (counted
        # ``frames_deduped``, outside the ledger like rejections), so
        # duplicated transports, retries and hedge re-sends can never
        # double-count the ledger or double-publish a result from this
        # replica. 0 disables; frames without a fid always pass.
        dedup_window: int = 4096,
    ):
        self.pipeline = pipeline
        self.connector = connector
        self.similarity_threshold = float(similarity_threshold)
        self.subject_names = list(subject_names) if subject_names else []
        self.metrics = metrics or Metrics()
        self.resilience = resilience or ResiliencePolicy()
        self._faults = fault_injector
        self._backend_probe_fn = backend_probe_fn
        self._cpu_fallback = cpu_fallback
        if frame_shape is None:
            raise ValueError("frame_shape (H, W) is required (static device shapes)")
        self.admission = admission
        if self.admission is not None and self.admission.inflight_fn is None:
            # The bounded intake reads the admission ledger: in-system =
            # admitted - completed - Σ drops (always current, no second
            # bookkeeping to desync).
            self.admission.inflight_fn = self.frames_in_system
        self.brownout_policy = brownout
        self.journal = dead_letter_journal
        self.state = state_store
        self._brownout_level = 0
        self._queue_wait_ewma: Optional[float] = None
        self._brownout_changed_at = 0.0
        self._bulk_seq = 0
        # Aggregated backpressure announcements: one ``rejected`` status
        # per reason per window, carrying the count since the last one —
        # per-frame publishes would amplify the very flood being shed.
        self._reject_note_interval_s = 0.5
        self._reject_pending: Dict[str, int] = {}
        self._reject_last_pub: Dict[str, float] = {}
        self._reject_lock = threading.Lock()
        self.tracer = tracer
        # Busy time of the serving loop's current iteration by leaf
        # (``_leaf``), and the span id of the batch's open ``dispatch``
        # span, which its leaves name as parent. Touched by the loop's
        # thread only.
        self._loop_busy: Dict[str, float] = {}
        self._dispatch_span = 0
        # The readback worker's CPU clock at its last ``_publish``'s end
        # (``readback_cpu_s`` runs from there), and for each thread that
        # runs ``_on_frame`` its frames since it last read its CPU clock
        # and that reading (``intake_thread_cpu_s``).
        self._publish_cpu_mark: Optional[float] = None
        self._intake_cpu_marks: Dict[int, List[float]] = {}
        # The batch whose gate went to the device ahead of the step before
        # it (``_serve_one``), until the next iteration serves it — kept
        # across a crash of the loop for the restarted one — and the
        # stage-1 scores enqueued and not yet read, with the staging
        # buffer they were computed from (``_gate_enqueue``).
        self._ahead: Optional[_Held] = None
        self._gate_pending: Optional[tuple] = None
        # Smoothed seconds an iteration of the loop waits for the chip
        # (``gate_wait``: the scores come back behind whatever the device
        # had queued) and lasts: their ratio is ``CHIP_BOUND_SHARE``'s.
        self._chip_wait_s = 0.0
        self._iteration_s = 0.0
        self.slo = slo_monitor
        self.replica = replica
        # Embedder-rollout coordinator (runtime.rollout.RolloutCoordinator),
        # attached by the rollout orchestration when a dual-score parity
        # window is live: _publish samples detected face crops into it
        # (rate-limited, copied, scored on the rollout thread — the hot
        # path pays one attribute read when unset). None = no rollout.
        self.rollout = None
        # Versioned model registry (runtime.registry.ModelRegistry) and
        # the in-flight swap coordinator, attached by the registry
        # orchestration. When ``registry`` is set, published results and
        # the tracker key on the FULL registry stamp (every role), so any
        # role's cutover invalidates cached identity verdicts; when
        # ``registry_swap`` is live, _publish samples whole frames + the
        # serving detector's verdicts into its detection-parity window
        # (same rate-limited, fail-open contract as ``rollout``). Both
        # cost one attribute read on the hot path when unset.
        self.registry = None
        self.registry_swap = None
        # Serving-loop progress stamp, refreshed every loop iteration
        # (batch AND idle — get_batch's flush timeout guarantees regular
        # iterations even with zero traffic). Read by the loop_liveness
        # gauge SLO through ``loop_staleness_s``: empty latency windows
        # read as "no breach", so without this a wedged loop scores a
        # clean /health forever — the gauge is what lets the expo
        # backstop's tick escalate a loop that stopped moving.
        self._loop_progress_t: Optional[float] = None
        # Recompile-watchdog arming flag: only set once warmup() compiled
        # the whole bucket ladder — before that, a jit-cache miss is the
        # expected cost of starting up, not a mid-serving compile.
        self._warmed = False
        # Cascade early-exit gate (ISSUE 13): active iff enabled AND the
        # pipeline carries a stage-1 model. The threshold resolves
        # knob > gate's trained operating point > module default.
        gate = getattr(pipeline, "cascade", None)
        self._cascade_active = (bool(cascade) and gate is not None
                                and hasattr(pipeline, "cascade_scores"))
        if cascade_threshold is None:
            cascade_threshold = getattr(gate, "threshold", None)
        self.cascade_threshold = float(
            DEFAULT_CASCADE_THRESHOLD if cascade_threshold is None
            else cascade_threshold)
        self.cascade_brownout_notch = float(cascade_brownout_notch)
        self.tracker = tracker
        # Cumulative scored/rejected counts behind the /prom rate gauges
        # (serving-thread only — no lock needed).
        self._cascade_scored = 0
        self._cascade_rejected = 0
        self._bucket_ladder = self._build_bucket_ladder(bucket_sizes,
                                                        int(batch_size))
        # Ingest subsystem (runtime.ingest): staging ring sized per
        # dispatch-bucket rung + mode-derived transfer dtype + (jpeg)
        # decode pool. Built BEFORE the batcher, which stages into it.
        self.ingest = None
        if ingest is not None:
            self.ingest = IngestPipeline(
                ingest, self._bucket_ladder, tuple(frame_shape),
                metrics=self.metrics, tracer=tracer,
                trace_topic=FRAME_TOPIC, fault_injector=fault_injector,
                inflight_depth=int(inflight_depth))
            transfer_dtype = self.ingest.transfer_dtype
            # On a mesh of several chips the step takes its frames on
            # every chip of a tp group: upload straight to that placement.
            placement = getattr(pipeline, "frames_sharding", None)
            if placement is not None and len(placement.device_set) > 1:
                self.ingest.upload_device = placement
            if (self.admission is not None
                    and self.admission.staging_free_fn is None):
                # Ring exhaustion backpressures at the front door: a
                # flood that outruns recycle is rejected explicitly
                # (reason ``staging``), never absorbed by an allocation.
                self.admission.staging_free_fn = self.ingest.staging.free_slots
        self.batcher = FrameBatcher(batch_size, frame_shape, flush_timeout,
                                    dtype=transfer_dtype,
                                    metrics=self.metrics,
                                    fault_injector=fault_injector,
                                    target_latency_s=target_latency_s,
                                    stale_after_s=shed_stale_after_s,
                                    drop_log=self._journal_drop,
                                    tracer=tracer,
                                    trace_topic=FRAME_TOPIC,
                                    staging_ring=(self.ingest.staging
                                                  if self.ingest is not None
                                                  else None))
        self.inflight_depth = int(inflight_depth)
        self._inflight: deque = deque()
        # One condition variable guards the in-flight queue AND the
        # completion counter: the dispatch loop appends + waits for slots,
        # the readback worker pops + notifies, drain() waits on it instead
        # of a blind sleep.
        self._inflight_cv = threading.Condition()
        self._blocker: Optional[_ReadbackBlocker] = None
        self._worker: Optional[threading.Thread] = None
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._crashed = False
        self._consecutive_dispatch_failures = 0
        self._degraded = False
        # Completion counter paired with batcher.delivered_batches: a batch
        # counts as completed only once PUBLISHED (or abandoned on dispatch
        # failure / dead-lettered / lost to a crash — every exit settles
        # it), so drain() sees every popped batch through its whole
        # lifetime — there is no window where a batch in hand is invisible
        # (round-2 advisor #3: a bare _dispatching flag had one between
        # get_batch() and the flag write).
        self._completed_batches = 0
        self._enrolment: Optional[_Enrolment] = None
        self._enrol_lock = threading.Lock()
        # Called (no args, best-effort) after every COMMITTED gallery
        # change — a finished enrolment, a reload_gallery swap. This is a
        # direct callback, not a status-topic subscription, deliberately:
        # wire connectors (JSONL/socket) publish outbound only and never
        # dispatch their own publishes to local subscribers, so a
        # supervisor listening on STATUS_TOPIC would never hear commits in
        # production. ServiceSupervisor registers its checkpoint here.
        self.commit_hooks: List[Callable[[], None]] = []
        if self.state is not None:
            # The lifecycle reads the LIVE pipeline's gallery at
            # checkpoint time (reload/CPU-fallback may swap it) and nudges
            # its thresholds through the commit hooks just registered.
            self.state.attach(self)
            # Degraded-durability announcements (ISSUE 15) ride the same
            # status channel as the dispatch-side degraded mode: wire the
            # monitor's publish hook unless the app already did.
            dur = getattr(self.state, "durability", None)
            if dur is not None and dur.publish is None:
                dur.publish = self._publish_status

        # Enrolment embeds ride a FIXED-size padded chunk: one compiled
        # shape, warmed at start(), so an enroll command never triggers a
        # mid-serving XLA compile.
        self._enrol_chunk = 8

        def _embed_chunk(params, crops):
            from opencv_facerecognizer_tpu.models.embedder import normalize_faces

            return self.pipeline.embed_net.apply(
                {"params": params},
                normalize_faces(crops, self.pipeline.face_size),
            )

        import jax

        self._embed_chunk = jax.jit(_embed_chunk)  # ocvf-lint: boundary=jit-recompile-hazard -- built once at construction for ONE fixed chunk shape; warmup() compiles it before serving starts
        # Placement override for the enrolment graph. None = default
        # backend. rebuild_pipeline_on_cpu pins this to the CPU device it
        # rebuilt on: the bare jit above takes uncommitted numpy inputs
        # and would otherwise keep dispatching enrolment embeds on the
        # dead accelerator after a CPU fallback.
        self._embed_device = None

        # Idempotent-intake window (ISSUE 16): fids of ADMITTED frames,
        # set for O(1) membership + deque for FIFO eviction. Sized so a
        # legitimately re-sent frame (hedge, retry after a partition
        # heals) is still remembered long after its twin completed —
        # the window bounds memory, not correctness, because a fid that
        # was evicted AND re-delivered that late would need > window
        # admissions in between.
        self._dedup_window = max(0, int(dedup_window))
        self._dedup_seen: set = set()
        self._dedup_order: deque = deque()
        self._dedup_lock = threading.Lock()

        connector.subscribe(FRAME_TOPIC, self._on_frame)
        connector.subscribe(CONTROL_TOPIC, self._on_control)
        connector.subscribe(LINK_PING_TOPIC, self._on_link_ping)

    def _build_bucket_ladder(self, bucket_sizes, batch_size: int) -> List[int]:
        """Ascending dispatch sizes, always ending at ``batch_size``. Only
        ladder entries the mesh can shard (divisible by every dp axis the
        pipeline dispatches over) survive the filter."""
        divisor = 1
        try:
            from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS

            for mesh in (getattr(getattr(self.pipeline, "gallery", None),
                                 "mesh", None),
                         getattr(self.pipeline, "mesh_a", None)):
                if mesh is not None:
                    divisor = max(divisor, int(mesh.shape[DP_AXIS]))
        except Exception:  # ocvf-lint: disable=swallowed-exception -- config probe at construction: stub/fake pipelines legitimately have no mesh, divisor=1 is the documented fallback
            divisor = 1
        ladder = {int(b) for b in (bucket_sizes or ())
                  if 0 < int(b) < batch_size and int(b) % divisor == 0}
        ladder.add(batch_size)
        return sorted(ladder)

    def _pick_bucket(self, count: int) -> int:
        for b in self._bucket_ladder:
            if count <= b:
                return b
        return self.batcher.batch_size

    # ---- admission ledger (overload layer §4) ----

    #: every way an ADMITTED frame can leave the system other than being
    #: published: the ledger invariant is
    #: ``frames_admitted == frames_completed + Σ(these)`` once the system
    #: is quiescent (``in_system`` = the live remainder otherwise).
    #: Pre-admission rejections (``frames_rejected_*``) are outside by
    #: design — a rejected frame never entered.
    LEDGER_DROP_COUNTERS = mn.LEDGER_DROP_COUNTERS

    def ledger(self) -> Dict[str, Any]:
        """One atomic admission-ledger snapshot: ``admitted``,
        ``completed``, ``completed_empty`` (cascade early exits — frames
        published with an empty face list because stage 1 scored them
        face-free; terminal completions, not drops), ``completed_cached``
        (track-cache exits, ISSUE 17: published with the cached
        identities, never dispatched — terminal completions too),
        per-reason ``drops_by_reason`` and the ``in_system`` remainder
        (frames admitted but not yet finished — queued in the batcher,
        riding an in-flight batch, or mid-publish). The invariant is
        ``admitted == completed + completed_empty + completed_cached +
        Σ drops`` at quiescence (after ``drain()``, ``in_system`` must be
        exactly 0) — chaos_soak and the overload/cascade/tracker tests
        enforce it."""
        c = self.metrics.counters()
        drops = {name: c[name] for name in self.LEDGER_DROP_COUNTERS
                 if c.get(name)}
        admitted = c.get(mn.FRAMES_ADMITTED, 0.0)
        completed = c.get(mn.FRAMES_COMPLETED, 0.0)
        completed_empty = c.get(mn.FRAMES_COMPLETED_EMPTY, 0.0)
        completed_cached = c.get(mn.FRAMES_COMPLETED_CACHED, 0.0)
        return {
            "admitted": admitted,
            "completed": completed,
            "completed_empty": completed_empty,
            "completed_cached": completed_cached,
            "drops_by_reason": drops,
            "in_system": (admitted - completed - completed_empty
                          - completed_cached - sum(drops.values())),
        }

    def frames_in_system(self) -> float:
        """Admitted-but-unfinished frame count (the admission bound's
        signal). One atomic allocation-free counter read (this runs per
        offered frame on the connector thread, under exactly the flood it
        exists to shed); it can transiently lag a frame mid-transition
        between buckets — fine for a bound, exactness is only claimed at
        quiescence."""
        return max(0.0, self.metrics.sum_counters(
            (mn.FRAMES_ADMITTED,),
            (mn.FRAMES_COMPLETED, mn.FRAMES_COMPLETED_EMPTY,
             mn.FRAMES_COMPLETED_CACHED)
            + self.LEDGER_DROP_COUNTERS))

    def _journal_drop(self, reason: str, entries: List[Dict[str, Any]],
                      **extra) -> None:
        """Append shed/lost frames to the dead-letter journal (no-op
        without one). Also the batcher's ``drop_log`` hook. Entries carry
        ``trace_id`` + the ``stage`` the frame died at, so a replay can
        reconstruct exactly where each dropped frame's lifecycle ended."""
        if self.journal is not None:
            self.journal.append(reason, entries, **extra)

    @staticmethod
    def _drop_entries(metas, enqueue_ts, trace_ids, stage: str,
                      priority=None) -> List[Dict[str, Any]]:
        """Journal entries for a run of dropped frames, aligned by index
        (missing provenance lists degrade to None fields, same as the
        pre-tracing rows)."""
        n = len(metas)
        return [{
            "meta": metas[i],
            "enqueue_ts": (enqueue_ts[i] if enqueue_ts is not None
                           and i < len(enqueue_ts) else None),
            "priority": priority,
            "trace_id": (trace_ids[i] or None) if trace_ids is not None
                        and i < len(trace_ids) else None,
            "stage": stage,
        } for i in range(n)]

    def _trace_settle(self, trace_ids, outcome: str, where: str,
                      batch: int = 0) -> None:
        """Terminal ``settle`` span for each traced frame in the run —
        every admitted frame must emit exactly one, with ``outcome``
        either ``completed`` or the ledger drop counter it landed in (the
        span-level mirror of the admission-ledger invariant)."""
        tracer = self.tracer
        if tracer is None:
            return
        for tid in trace_ids or ():
            if tid:
                tracer.emit(tid, tracing.SETTLE_STAGE, topic=FRAME_TOPIC,
                            outcome=outcome, where=where, batch=batch)

    def _note_rejection(self, reason: str) -> None:
        """Count + (rate-limited) announce one admission rejection. The
        status message aggregates everything since the last announcement
        for that reason — a backpressure signal, not a per-frame echo, so
        it carries no per-frame fields (an aggregated window mixes
        priorities; stamping one would mislead a consumer throttling a
        specific producer class)."""
        self.metrics.incr(mn.FRAMES_REJECTED_PREFIX + reason)
        now = time.monotonic()
        with self._reject_lock:
            self._reject_pending[reason] = self._reject_pending.get(reason, 0) + 1
            last = self._reject_last_pub.get(reason, 0.0)
            if now - last < self._reject_note_interval_s:
                return
            count = self._reject_pending.pop(reason)
            self._reject_last_pub[reason] = now
        self._publish_status({"status": "rejected", "reason": reason,
                              "count": count})

    def _flush_rejections(self, force: bool = False) -> None:
        """Trailing-edge flush of aggregated rejections: when a flood
        stops mid-window, the counts still pending would otherwise never
        be announced (only a LATER rejection of the same reason triggers a
        publish). Called from the serving loop's idle tick; stop() forces
        a final flush regardless of the window."""
        now = time.monotonic()
        flush = []
        with self._reject_lock:
            for reason in list(self._reject_pending):
                if force or (now - self._reject_last_pub.get(reason, 0.0)
                             >= self._reject_note_interval_s):
                    flush.append((reason, self._reject_pending.pop(reason)))
                    self._reject_last_pub[reason] = now
        for reason, count in flush:
            self._publish_status({"status": "rejected", "reason": reason,
                                  "count": count})

    # ---- brownout controller (overload layer §2) ----

    @property
    def brownout_level(self) -> int:
        return self._brownout_level

    def _note_queue_wait(self, seconds: float) -> None:
        """Feed the brownout controller's queue-wait EWMA (called per
        batch with the batch's mean queue wait, and with 0.0 on idle ticks
        so an emptied queue recovers even when traffic stops entirely)."""
        if self.brownout_policy is None:
            return
        policy = self.brownout_policy
        prev = self._queue_wait_ewma
        self._queue_wait_ewma = (seconds if prev is None
                                 else prev + policy.ewma_alpha * (seconds - prev))
        self._update_brownout()

    def _update_brownout(self) -> None:
        policy = self.brownout_policy
        now = time.monotonic()
        if now - self._brownout_changed_at < policy.dwell_s:
            return  # hysteresis dwell: no flapping between batches
        ewma = self._queue_wait_ewma or 0.0
        level = self._brownout_level
        if ewma > policy.queue_wait_s and level < policy.max_level:
            self._set_brownout(level + 1, ewma)
        elif ewma < policy.exit_ratio * policy.queue_wait_s and level > 0:
            self._set_brownout(level - 1, ewma)

    def _set_brownout(self, level: int, ewma: float) -> None:
        prev = self._brownout_level
        self._brownout_level = level
        self._brownout_changed_at = time.monotonic()
        self.metrics.set_gauge(mn.BROWNOUT_LEVEL, level)
        if self.tracer is not None:
            # Instant lifecycle span: level transitions are the overload
            # story's causal markers (a queue-wait balloon followed by a
            # brownout span explains the shed settle spans after it).
            self.tracer.emit(self.tracer.new_trace(), "brownout",
                             topic=tracing.LIFECYCLE_TOPIC, level=level,
                             from_level=prev,
                             queue_wait_ewma_ms=round(ewma * 1e3, 2))
        if level > 0:
            self.metrics.incr(mn.BROWNOUT_TRANSITIONS)
            self._publish_status({"status": "brownout", "level": level,
                                  "queue_wait_ewma_ms": round(ewma * 1e3, 2)})
        else:
            self.metrics.incr(mn.BROWNOUT_RECOVERIES)
            self._publish_status({"status": "brownout_recovered",
                                  "queue_wait_ewma_ms": round(ewma * 1e3, 2)})

    def _effective_brownout_level(self) -> int:
        """The controller's level, plus one when the SLO monitor reads
        critical — the health verdict as a brownout INPUT: a blown error
        budget sheds bulk intake even before the queue-wait EWMA catches
        up, and stops the moment health de-escalates. Only the intake
        skip consumes the boost; the controller's own level/hysteresis
        (and its recovery) are untouched, so SLO pressure can never wedge
        the brownout state machine."""
        level = self._brownout_level
        if (self.slo is not None and self.brownout_policy is not None
                and self.slo.state_code >= STATE_CRITICAL):
            level = min(self.brownout_policy.max_level, level + 1)
        return level

    def _brownout_sheds_intake(self, priority: int, level: int) -> bool:
        """Shed this (already admitted) frame at intake? Interactive
        frames never (the intake skip is the priority-aware half of
        brownout; the level-2 ladder trim in ``_serve_one`` is the
        class-blind half — see BrownoutPolicy's docstring); bulk frames
        skip-k at level 1, always at ``max_level``. ``level`` is the
        caller's one ``_effective_brownout_level()`` read (incl. the SLO
        critical-health boost) — the same read is journaled with the
        drop, so the recorded level is the one that caused it."""
        if level <= 0 or priority <= PRIORITY_INTERACTIVE:
            return False
        if level >= self.brownout_policy.max_level:
            return True
        self._bulk_seq += 1
        return self._bulk_seq % max(2, self.brownout_policy.bulk_skip) != 0

    def _brownout_bucket_cap(self) -> Optional[int]:
        """At max brownout level the dispatch ladder is capped at its
        smallest rung (one small fast device call per batch); else None."""
        if (self.brownout_policy is not None
                and self._brownout_level >= self.brownout_policy.max_level):
            return self._bucket_ladder[0]
        return None

    # ---- the serving loop's leaves (busy time + spans) ----

    def _leaf(self, stage: str, batch_tid: int = 0,
              parent: Optional[int] = None, **attrs) -> _Leaf:
        """``with self._leaf(stage, batch_tid):`` around one leaf of the
        loop's iteration, on the loop's thread: always counts the block's
        seconds (``loop_s_<stage>``), and for a traced batch records the
        span ``stage`` under the open ``dispatch`` span (or ``parent``)."""
        span = tracing.NULL_SPAN
        if batch_tid:
            span = self.tracer.span(  # ocvf-lint: disable=resource-pairing -- entered and left by the _Leaf returned here, which every caller uses as `with self._leaf(...)`
                batch_tid, stage,
                parent=self._dispatch_span if parent is None else parent,
                **attrs)
        return _Leaf(self._loop_busy, stage, span)

    def _flush_loop_busy(self, wall: float, cpu: float) -> None:
        """One ``incr`` per leaf the iteration passed, the rest of its
        wall time under ``loop_s_unnamed``: the counters tile the loop's
        time, so a window's deltas say where a lost second sat. Beside
        them ``loop_cpu_s``, the seconds of CPU the loop's thread ran in
        the iteration (``cpu``): the wall time outside the waits the loop
        makes by design, less it, is what the thread waited for the
        interpreter or a lock. One read of the CPU clock an iteration,
        not two a leaf: it is a system call, microseconds where the wall
        clock takes a twentieth of one (PERF.md section 6, PR 41)."""
        busy = self._loop_busy
        self._chip_wait_s += CHIP_BOUND_ALPHA * (
            busy.get("gate_wait", 0.0) - self._chip_wait_s)
        self._iteration_s += CHIP_BOUND_ALPHA * (wall - self._iteration_s)
        named = 0.0
        for stage, seconds in busy.items():
            self.metrics.incr(mn.LOOP_S_PREFIX + stage, seconds)
            named += seconds
        busy.clear()
        self.metrics.incr(mn.LOOP_S_PREFIX + "unnamed",
                          max(0.0, wall - named))
        self.metrics.incr_many((mn.LOOP_CPU_S, cpu), (mn.LOOP_BATCHES, 1.0))

    # ---- cascade early-exit gate (ISSUE 13) ----

    def _effective_cascade_threshold(self) -> float:
        """The stage-1 operating threshold, tightened one notch while
        brownout pressure is on (effective level >= 1, incl. the SLO
        critical boost): rejecting borderline frames at stage 1 is the
        cheapest possible shed — it saves whole stage-2 dispatches
        BEFORE the intake skip starts dropping admitted frames
        outright. The gauge on /prom always shows the EFFECTIVE value."""
        thr = self.cascade_threshold
        if (self.brownout_policy is not None and self.cascade_brownout_notch
                and self._effective_brownout_level() >= 1):
            thr = min(0.99, thr + self.cascade_brownout_notch)
        return thr

    def _gate_enqueue(self, frames, count: int, batch_tid: int) -> None:
        """Stage 1 over the batch's dispatch rung, put on the device's
        queue and not read: the scores wait in ``_gate_pending`` for
        ``_cascade_keep_mask``, so the loop may enqueue other work (the
        batch before's step) between the two. An enqueue that raises
        leaves nothing pending, and the batch fails OPEN to the full
        chain (the cascade may save device time, never cost
        availability)."""
        self._gate_pending = None
        bucket = self._pick_bucket(count)
        view = frames[:bucket] if bucket < len(frames) else frames
        t0 = time.monotonic()
        try:
            with self._leaf("gate_enqueue", batch_tid):
                scores = self.pipeline.cascade_scores(view)
        except Exception:  # noqa: BLE001 — fail open: stage 2 serves the batch
            logging.getLogger(__name__).exception(
                "cascade stage-1 scoring failed; serving the full batch")
            self.metrics.incr(mn.CASCADE_ERRORS)
            return
        info = getattr(self.pipeline, "last_cascade_info", None) or {}
        if self._warmed and info.get("cache_hit") is False:
            self._note_recompile(bucket, count, "cascade")
        self._gate_pending = (frames, scores, time.monotonic() - t0)

    def _cascade_keep_mask(self, frames, count: int,
                           batch_tid: int) -> Optional[np.ndarray]:
        """Read the stage-1 scores that ``_gate_enqueue`` put on the
        device for this batch: returns the per-frame keep mask (True =
        face-possible, survives to the full detector) for the first
        ``count`` frames, or None when stage 1 is unavailable this batch
        — a scoring error fails OPEN to the full chain. The tiny
        [B]-float readback here IS the early-exit decision point, and
        the fence of the staging buffer's first H2D read; the host wall
        of both halves lands in the ``cascade_score`` window."""
        pending, self._gate_pending = self._gate_pending, None
        if pending is None or pending[0] is not frames:
            return None  # the enqueue failed, and was counted: fail open
        _frames, scores, enqueue_s = pending
        thr = self._effective_cascade_threshold()
        with (self.tracer.span(batch_tid, "cascade",
                               parent=self._dispatch_span, frames=count,
                               threshold=round(thr, 4))
              if batch_tid else tracing.NULL_SPAN) as cascade:
            t0 = time.monotonic()
            try:
                # The readback waits for whatever the device still has
                # queued ahead of stage 1, then for the scores' way back.
                with self._leaf("gate_wait", batch_tid, parent=cascade.id):
                    scores = np.asarray(scores)  # ocvf-lint: boundary=host-sync -- the cascade's designed decision readback: a [B]-float materialize whose entire purpose is deciding whether the expensive stage-2 dispatch happens at all (ISSUE 13)
            except Exception:  # noqa: BLE001 — fail open: stage 2 serves the batch
                logging.getLogger(__name__).exception(
                    "cascade stage-1 readback failed; serving the full batch")
                self.metrics.incr(mn.CASCADE_ERRORS)
                return None
            self.metrics.observe(mn.CASCADE_SCORE,
                                 enqueue_s + time.monotonic() - t0)
            keep = scores[:count] >= thr
            if self._faults is not None:
                # Chaos boundary: ``cascade: reject_all`` forces the
                # pathological all-face-free verdict (runtime.faults).
                keep = self._faults.on_cascade(keep)
            rejected = count - int(keep.sum())
            self._cascade_scored += count
            self._cascade_rejected += rejected
            self.metrics.incr(mn.CASCADE_FRAMES_SCORED, count)
            reject_rate = self._cascade_rejected / max(1, self._cascade_scored)
            self.metrics.set_gauge(mn.CASCADE_REJECT_RATE, reject_rate)
            self.metrics.set_gauge(mn.CASCADE_PASS_RATE, 1.0 - reject_rate)
            self.metrics.set_gauge(mn.CASCADE_THRESHOLD, thr)
            cascade.attrs["rejected"] = rejected
        return keep

    def _note_gate_misses(self, rejected, batch_tid: int) -> None:
        """A face-free verdict on a tracked stream is a miss for its
        live tracks: a vanished subject ages out within the miss TTL
        instead of being served from a stale cache entry. The tracker is
        told when the verdict is read — ahead of the step's enqueue, so
        ahead of the ``tracker.update`` calls of that step's results, and
        of every later lookup; the publish (``_complete_empty``) follows
        the enqueue. One acquisition of the tracker's lock a batch (the
        readback thread holds it for bookkeeping only): ``track_miss``."""
        with self._leaf("track_miss", batch_tid, frames=len(rejected)):
            keys = [key for key in (self._track_stream_key(row[0])
                                    for row in rejected) if key is not None]
            if keys:
                try:
                    self.tracker.note_misses(keys)
                except Exception:  # noqa: BLE001 — observation only
                    self.metrics.incr(mn.TRACK_ERRORS)

    def _complete_empty(self, rejected, batch_tid: int) -> None:
        """Settle cascade-rejected frames as ``completed_empty``: each
        publishes a result with an empty face list (producers get an
        answer for every admitted frame — the uplift bench counts
        completions through the same result stream) and lands in the
        ledger's ``completed_empty`` bucket with a terminal settle span.
        ``rejected`` rows are ``(meta, enqueue_ts, trace_id, priority)``.
        A crash escaping mid-run settles the remainder as crashed,
        exactly like ``_publish`` — no frame is ever left in limbo."""
        published = 0
        try:
            for meta, _ts, _tid, _pri in rejected:
                self.connector.publish(RESULT_TOPIC,
                                       {"meta": meta, "faces": [],
                                        "exit": "cascade"})
                published += 1
        finally:
            self.metrics.incr(mn.FRAMES_COMPLETED_EMPTY, published)
            self._trace_settle([r[2] for r in rejected[:published]],
                               tracing.OUTCOME_COMPLETED_EMPTY,
                               "cascade.reject", batch=batch_tid)
            if published < len(rejected):
                self.metrics.incr(mn.FRAMES_DROPPED_CRASHED,
                                  len(rejected) - published)
                self._trace_settle([r[2] for r in rejected[published:]],
                                   mn.FRAMES_DROPPED_CRASHED,
                                   "cascade.publish_crashed",
                                   batch=batch_tid)
            # Early exits are real end-to-end completions: their latency
            # belongs in the SLO histograms like any published frame.
            now_mono = time.monotonic()
            for _meta, ts, _tid, pri in rejected[:published]:
                if ts is not None:
                    self._observe_e2e(ts, pri, now_mono)

    # ---- temporal identity cache (ISSUE 17) ----

    @staticmethod
    def _track_stream_key(meta):
        """The tracking scope of one frame: its camera stream/topic from
        ``meta`` (``stream`` preferred, ``topic`` accepted — the same key
        PR 10's rendezvous routing pins to one replica). None = the frame
        is untracked (no cache lookup, no track update) — frames without
        a stream identity can never alias each other's tracks."""
        if isinstance(meta, dict):
            key = meta.get("stream")
            if key is None:
                key = meta.get("topic")
            return key
        return None

    def _track_reverify_stretch(self) -> float:
        """Brownout composition (mirrors the cascade threshold notch): at
        effective level >= 1 the re-verify interval stretches by the
        tracker's configured factor — serving MORE frames from the cache
        (bounded staleness) is a cheaper shed than dropping admitted
        intake outright."""
        if (self.tracker is not None and self.brownout_policy is not None
                and self._effective_brownout_level() >= 1):
            return float(self.tracker.config.brownout_stretch)
        return 1.0

    def _model_stamp(self, gallery_ver):
        """The tracker/publish model stamp: the plain embedder version
        when no registry is wired (PR 17 behavior, unchanged), else the
        FULL registry stamp as a sorted (role, version) tuple with the
        embedder slot overridden by the dispatch-time gallery version.
        The tracker compares stamps by opaque equality, so keying on the
        tuple makes ANY role's cutover invalidate cached identity
        verdicts — a new detector changes which faces exist, not just
        their embeddings."""
        reg = self.registry
        if reg is None:
            return gallery_ver
        stamp = reg.stamp()
        if gallery_ver is not None:
            stamp["embedder"] = int(gallery_ver)
        return tuple(sorted(stamp.items()))

    @staticmethod
    def _stamp_fields(stamp):
        """Split a model stamp into its published fields: the plain int
        ``embedder_version`` and, when the stamp is a full registry
        tuple, the role->version dict for ``payload["registry"]``."""
        if isinstance(stamp, tuple):
            roles = {str(k): int(v) for k, v in stamp}  # ocvf-lint: boundary=host-sync -- stamps are plain Python ints (registry manifest versions + the gallery's host-side version counter); nothing device-resident ever enters a stamp tuple
            emb = roles.get("embedder")
            return emb, roles
        return stamp, None

    def flush_model_caches(self, stamp=None, reason: str = "registry"
                           ) -> int:
        """Eager identity-cache invalidation on a registry cutover (the
        swap coordinator's ``flush_fn``): every cached tracker verdict
        was produced by the pre-swap model set, so flush now instead of
        waiting for each track's lazy stamp-mismatch eviction. The
        cascade's per-frame verdicts live in the same served results, so
        the tracker flush covers both PR 17 and PR 13 caches; the jit
        COMPILE caches are untouched — params are call arguments, a
        same-architecture swap never recompiles. Returns tracks
        flushed."""
        del stamp  # the flush is total; the stamp is provenance only
        flushed = 0
        if self.tracker is not None:
            try:
                flushed = self.tracker.flush_all(reason=reason)
            except Exception:  # noqa: BLE001 — cache only, fail open
                logging.getLogger(__name__).exception(
                    "tracker flush on registry cutover failed")
                self.metrics.incr(mn.TRACK_ERRORS)
        self.metrics.incr(mn.REGISTRY_CACHE_FLUSHES)
        return flushed

    def _track_lookup(self, meta, frame, gallery_ver, stretch: float):
        """One fail-open cache consult: the cached payload or None. A
        tracker bug must cost the cache win, never the frame — the full
        pipeline is always the safe answer."""
        key = self._track_stream_key(meta)
        if key is None:
            return None
        try:
            return self.tracker.lookup(key, frame,
                                       embedder_version=gallery_ver,
                                       reverify_stretch=stretch)
        except Exception:  # noqa: BLE001 — fail open to the full path
            logging.getLogger(__name__).exception("tracker lookup failed")
            self.metrics.incr(mn.TRACK_ERRORS)
            return None

    def _complete_cached(self, cached, batch_tid: int) -> None:
        """Settle track-cache hits as ``completed_cached``: each
        publishes the cached identities (``exit: track_cache`` plus the
        serving ``track_id``) and lands in the ledger's
        ``completed_cached`` bucket with a terminal settle span — the
        ``_complete_empty`` pattern (ISSUE 13) for the cache exit.
        ``cached`` rows are ``(meta, enqueue_ts, trace_id, priority,
        hit)`` where ``hit`` is the tracker's lookup payload. A crash
        escaping mid-run settles the remainder as crashed."""
        published = 0
        try:
            for meta, _ts, _tid, _pri, hit in cached:
                payload = {"meta": meta, "faces": hit["faces"],
                           "exit": "track_cache",
                           "track_id": hit["track_id"]}
                emb_ver, reg_roles = self._stamp_fields(
                    hit.get("embedder_version"))
                if emb_ver is not None:
                    payload["embedder_version"] = emb_ver
                if reg_roles is not None:
                    payload["registry"] = reg_roles
                self.connector.publish(RESULT_TOPIC, payload)
                published += 1
                self.metrics.incr(mn.FACES_FOUND, len(hit["faces"]))
        finally:
            self.metrics.incr(mn.FRAMES_COMPLETED_CACHED, published)
            self._trace_settle([r[2] for r in cached[:published]],
                               tracing.OUTCOME_COMPLETED_CACHED,
                               "track_cache.hit", batch=batch_tid)
            if published < len(cached):
                self.metrics.incr(mn.FRAMES_DROPPED_CRASHED,
                                  len(cached) - published)
                self._trace_settle([r[2] for r in cached[published:]],
                                   mn.FRAMES_DROPPED_CRASHED,
                                   "track_cache.publish_crashed",
                                   batch=batch_tid)
            # Cache exits are real end-to-end completions: their latency
            # belongs in the SLO histograms like any published frame.
            now_mono = time.monotonic()
            for _meta, ts, _tid, pri, _hit in cached[:published]:
                if ts is not None:
                    self._observe_e2e(ts, pri, now_mono)

    def _observe_e2e(self, enqueue_ts: float, priority: int,
                     now_mono: float) -> None:
        """One frame's end-to-end latency (batcher enqueue -> result
        publish) into the SLO histograms, split by priority class —
        shared by the publish path and the cascade's empty completions so
        the interactive objective sees every answered frame once."""
        e2e = now_mono - enqueue_ts
        self.metrics.observe(mn.E2E_LATENCY, e2e)
        if priority <= PRIORITY_INTERACTIVE:
            self.metrics.observe(mn.E2E_LATENCY_INTERACTIVE, e2e)

    def _note_recompile(self, bucket: int, frames_n: int, mode) -> None:
        """Recompile watchdog: a serving-path jit-cache miss AFTER
        warmup compiled the whole ladder (both cascade stages included)
        is a mid-serving XLA compile the prewarm design exists to
        prevent (a compile stalls that batch for seconds).
        Counted, spanned, and reported as a warn-level SLO event so
        /health shows it within one evaluation interval."""
        self.metrics.incr(mn.RECOMPILES_POST_WARMUP)
        if self.tracer is not None:
            self.tracer.emit(self.tracer.new_trace(), "recompile",
                             topic=tracing.LIFECYCLE_TOPIC, bucket=bucket,
                             frames=frames_n, mode=mode)
        if self.slo is not None:
            self.slo.note_event("recompile_post_warmup")

    def _run_embed_chunk(self, params, crops):
        """One fixed-size enrolment embed, honoring ``_embed_device``
        (``jax.default_device`` participates in the jit cache key, so the
        retargeted call compiles for — and runs on — the pinned device)."""
        import contextlib

        import jax

        ctx = (jax.default_device(self._embed_device)
               if self._embed_device is not None else contextlib.nullcontext())
        with ctx:
            return self._embed_chunk(params, crops)

    # ---- connector handlers (dispatch thread; keep cheap) ----

    def _on_frame(self, topic: str, message: Dict[str, Any]) -> None:
        # Connector-receive fault boundary: the injector may drop,
        # duplicate, flood, or corrupt the delivery (runtime.faults).
        messages = ([message] if self._faults is None
                    else self._faults.on_receive(message))
        tracer = self.tracer
        for msg in messages:
            t_in = time.monotonic()
            priority = parse_priority(msg.get("priority"))
            # Trace starts at receive: the span covers wire-decode (when
            # the connector stamped ``_recv_ts``) through the admission
            # verdict. tid 0 = sampled out; every emit below no-ops.
            tid = tracer.start_trace(topic) if tracer is not None else 0
            if tid:
                # ``_recv_ts`` is an optional producer/transport stamp
                # (monotonic) for wire transports that record parse time;
                # absent it, the receive span starts at handler entry.
                t_recv = msg.get("_recv_ts") or t_in
            # Idempotent intake (ISSUE 16): a fid this replica already
            # ADMITTED is refused before admission — like rejections,
            # dedup sits OUTSIDE the ledger, so a duplicated transport
            # or hedge re-send can never double-count it. Checked before
            # admit, recorded only AFTER admit succeeds: a frame whose
            # first delivery was rejected stays re-admittable on retry.
            meta = msg.get("meta")  # caller passthrough — ANY type
            fid = (meta.get("_fid")
                   if self._dedup_window and isinstance(meta, dict)
                   else None)
            if fid is not None and self._dedup_hit(fid):
                self.metrics.incr(mn.FRAMES_DEDUPED)
                if tid:
                    tracer.emit(tid, "receive", topic=topic, t0=t_recv,
                                dur=time.monotonic() - t_recv,
                                verdict="deduped", priority=priority)
                continue
            # Admission FIRST, decode second: a rejected frame must cost
            # ~nothing (the whole point of shedding at the front door).
            if self.admission is not None:
                reason = self.admission.admit(topic, priority)
                if reason is not None:
                    self._note_rejection(reason)
                    if tid:
                        # Rejected pre-admission: outside the ledger by
                        # design — the receive span IS the terminal one.
                        tracer.emit(tid, "receive", topic=topic, t0=t_recv,
                                    dur=time.monotonic() - t_recv,
                                    verdict="rejected_" + reason,
                                    priority=priority)
                    continue
            # Admitted: from here on the frame is the ledger's problem —
            # it must end as completed or as exactly one counted drop.
            if fid is not None:
                self._dedup_record(fid)
            self.metrics.incr(mn.FRAMES_ADMITTED)
            if tid:
                tracer.emit(tid, "receive", topic=topic, t0=t_recv,
                            dur=time.monotonic() - t_recv,
                            verdict="admitted", priority=priority)
            # ``intake``: from the verdict to the return of ``put`` —
            # the decode and the copy into staging. ``intake_s`` counts
            # the handler from its entry, beside ``frames_admitted``.
            with (tracer.span(tid, "intake", topic=topic) if tid
                  else tracing.NULL_SPAN):
                self._intake_admitted(msg, priority, tid)
            self.metrics.incr(mn.INTAKE_S, time.monotonic() - t_in)
            self._count_intake_thread_cpu()

    def _count_intake_thread_cpu(self) -> None:
        """``intake_thread_cpu_s``: the CPU of the thread that runs the
        handler, read off its own clock once in ``INTAKE_CPU_EVERY``
        admitted frames and counted whole (the clock is cumulative, so
        nothing between two reads is lost): in the handler and out of it,
        so an upper bound of the CPU inside ``intake_s``. A read a frame
        would cost the frame more than its bookkeeping does (PERF.md
        section 6, PR 41)."""
        ident = threading.get_ident()
        mark = self._intake_cpu_marks.get(ident)
        if mark is None:
            self._intake_cpu_marks[ident] = [0, time.thread_time()]
            return
        mark[0] += 1
        if mark[0] >= INTAKE_CPU_EVERY:
            cpu = time.thread_time()
            if cpu > mark[1]:  # not a new thread under an old identifier
                self.metrics.incr(mn.INTAKE_THREAD_CPU_S, cpu - mark[1])
            mark[0], mark[1] = 0, cpu

    def _intake_admitted(self, msg, priority: int, tid: int) -> None:
        """Decode one admitted frame and hand it to the batcher (or to
        the decode pool, on the compressed path)."""
        if JPEG_KEY in msg and (self.ingest is not None
                                and self.ingest.decoder is not None):
            # Compressed intake: hand the ADMITTED payload to the
            # decode pool — the connector thread never decodes. A
            # full decode queue is an explicit ledger drop (the
            # bounded-backlog mirror of the batcher's overflow).
            if not self.ingest.submit_decode(msg, priority, tid):
                self.metrics.incr(mn.FRAMES_DROPPED_DECODE)
                self._trace_settle([tid], mn.FRAMES_DROPPED_DECODE,
                                   "ingest.decode_backlog")
                self._journal_drop("decode_backlog", self._drop_entries(
                    [msg.get("meta")], None, [tid],
                    "ingest.decode_backlog", priority=priority))
            return
        # A JPEG payload with no decode pool falls through: the pixel
        # decode below fails and the frame counts malformed — the
        # operator forgot --ingest-mode jpeg, loudly.
        try:
            if "__frame__" in msg:
                frame, native_decoded = decode_frame_counted(msg)
                if native_decoded:
                    self.metrics.incr(mn.FRAMES_DECODED_NATIVE)
            else:
                frame = np.asarray(msg["frame"])
        except Exception:
            self.metrics.incr(mn.FRAMES_MALFORMED)
            self._trace_settle([tid], mn.FRAMES_MALFORMED, "decode")
            return
        self._intake_frame(frame, msg.get("meta"), priority, tid)

    def _dedup_hit(self, fid) -> bool:
        """True iff ``fid`` was already admitted within the window."""
        with self._dedup_lock:
            return fid in self._dedup_seen

    def _dedup_record(self, fid) -> None:
        """Remember an admitted fid; FIFO-evict past the window bound."""
        with self._dedup_lock:
            if fid in self._dedup_seen:
                return
            self._dedup_seen.add(fid)
            self._dedup_order.append(fid)
            while len(self._dedup_order) > self._dedup_window:
                self._dedup_seen.discard(self._dedup_order.popleft())

    def _on_link_ping(self, topic: str, message: Dict) -> None:
        """Link-supervision echo (ISSUE 16): bounce the router's ping
        payload back on the pong topic. Runs on the connector dispatch
        thread — proving exactly the path frames travel — and stays
        O(1): a replica too wedged to echo is, for routing purposes,
        down, which is the honest answer."""
        try:
            pong = dict(message) if isinstance(message, dict) else {}
            pong["replica"] = self.replica or pong.get("replica")
            self.connector.publish(LINK_PONG_TOPIC, pong)
        except Exception:  # ocvf-lint: disable=swallowed-exception -- a failed echo IS the signal: the router's pong deadline turns silence into a link-down verdict
            pass

    def _intake_frame(self, frame, meta, priority: int, tid: int) -> None:
        """Post-decode intake shared by the connector handler and the
        decode workers: brownout shed, then the batcher put. Runs on the
        connector's dispatch thread or a decode worker — keep cheap."""
        brownout_level = self._effective_brownout_level()
        if self._brownout_sheds_intake(priority, brownout_level):
            self.metrics.incr(mn.FRAMES_DROPPED_BROWNOUT)
            self._trace_settle([tid], mn.FRAMES_DROPPED_BROWNOUT,
                               "intake.brownout")
            # Journal the EFFECTIVE level (incl. the SLO critical
            # boost) — it is what caused this drop; the raw controller
            # level alone could read 0 here, hiding the cause.
            self._journal_drop("brownout", self._drop_entries(
                [meta], None, [tid], "intake.brownout",
                priority=priority),
                level=brownout_level)
            return
        if not self.batcher.put(frame, meta=meta,
                                priority=priority, trace_id=tid):
            self.metrics.incr(mn.FRAMES_DROPPED)

    def _intake_decoded(self, frame, message, priority: int,
                        tid: int) -> None:
        """Decode-pool success sink: the decoded pixel frame joins the
        normal intake (shape validation in the batcher still guards it —
        a camera sending the wrong resolution drops malformed, counted).
        Contains its own failures: the intake path's settlement effects
        (journal append, span emit) are non-raising by contract, so an
        exception here almost surely PRECEDED settlement — settling the
        frame as a decode drop is the right bias, and doing it HERE
        (where the ledger semantics live) keeps the pool's backstop from
        ever having to guess."""
        t_in = time.monotonic()
        try:
            self._intake_frame(frame, message.get("meta"), priority, tid)
        except Exception:  # noqa: BLE001 — an intake bug costs this frame's result, never a decode worker; the ledger settles it below
            logging.getLogger(__name__).exception(
                "decoded-frame intake failed; settling as decode drop")
            self._decode_failed(message, priority, tid, "decode_error")
        # The worker's share of ``intake_s`` (the decode itself reads
        # under ``decode_latency``).
        self.metrics.incr(mn.INTAKE_S, time.monotonic() - t_in)

    def _decode_failed(self, message, priority: int, tid: int,
                       reason: str) -> None:
        """Decode-pool failure sink: a corrupt/truncated compressed
        payload dead-letters with exact ledger settlement — one counted
        drop, one journal row, one terminal span."""
        self.metrics.incr(mn.FRAMES_DROPPED_DECODE)
        self._trace_settle([tid], mn.FRAMES_DROPPED_DECODE, "ingest.decode")
        self._journal_drop(reason, self._drop_entries(
            [message.get("meta")], None, [tid], "ingest.decode",
            priority=priority))

    def _on_control(self, topic: str, message: Dict[str, Any]) -> None:
        cmd = message.get("cmd")
        if cmd == "enroll" and self.replica is not None:
            # Read replicas fail enrollment closed: the writer lease owns
            # the WAL, and a reader mutating its local gallery outside the
            # replication stream would permanently fork it from the
            # writer's history.
            self.metrics.incr(mn.REPLICATION_ENROLL_REJECTED)
            self._publish_status({"status": "rejected",
                                  "reason": "read_replica",
                                  "detail": "enrollment is writer-only; "
                                            "route enroll to the writer "
                                            "replica"})
            return
        if cmd == "enroll":
            dur = getattr(self.state, "durability", None)
            if dur is not None and dur.degraded:
                # Refused CLOSED at the front door (ISSUE 15): while
                # durability is degraded an accepted enroll command would
                # collect crops only to fail its WAL append — the ack
                # never lies, so the refusal is explicit and immediate.
                self.metrics.incr(mn.ENROLLMENTS_REFUSED_DEGRADED)
                self._publish_status({
                    "status": "rejected",
                    "reason": "durability_degraded",
                    "detail": "enrollment refused: WAL durability is "
                              "degraded on this writer (serving "
                              "continues; re-arms automatically when "
                              "the probe sees the disk recover)"})
                return
            name = str(message.get("subject", f"subject_{len(self.subject_names)}"))
            count = int(message.get("count", 5))
            with self._enrol_lock:
                # The label is assigned (and subject_names grown) only when
                # _finish_enrolment succeeds — an abandoned or superseded
                # enrolment must not leave a name with zero gallery rows.
                self._enrolment = _Enrolment(name, count)
            self.connector.publish(STATUS_TOPIC, {"status": "enrolling", "subject": name,
                                                  "count": count})
        elif cmd == "stats":
            status = {"status": "stats",
                      **self.metrics.summary(),
                      **self.batcher.stats,
                      "degraded": self._degraded,
                      "brownout_level": self._brownout_level,
                      "ledger": self.ledger(),
                      "gallery_size": self.pipeline.gallery.size}
            if self.ingest is not None:
                status["ingest"] = self.ingest.stats()
            if self._cascade_active:
                status["cascade"] = {
                    "threshold": self.cascade_threshold,
                    "effective_threshold":
                        self._effective_cascade_threshold(),
                    "scored": self._cascade_scored,
                    "rejected": self._cascade_rejected,
                }
            if self.tracker is not None:
                status["tracks"] = self.tracker.stats()
            self.connector.publish(STATUS_TOPIC, status)

    # ---- lifecycle ----

    def start(self, warmup: bool = True) -> None:
        if self._thread is not None:
            return
        if warmup:
            self.warmup()
        # Install the dispatch fault boundary on the pipeline AFTER warmup:
        # the warmup compile must never consume a scripted chaos fault (or
        # randomly fail under soak rates) — only real serving batches cross
        # the boundary. stop() uninstalls, so a shared pipeline leaks no
        # injector into the next service built on it.
        if self._faults is not None:
            self.pipeline.fault_injector = self._faults
        # The wire decoder's library loads (in a checkout's first run,
        # builds) here, never on the first frame of a window.
        native.b64_available()
        self._running = True
        self._crashed = False
        self._loop_progress_t = None
        if self.ingest is not None:
            # Decode workers feed the same intake continuation the
            # connector thread uses; failures settle through the ledger.
            self.ingest.start(sink=self._intake_decoded,
                              on_error=self._decode_failed)
        self.connector.start()
        if self.state is not None:
            # Background durability ticker: watermarks + recovery probe
            # keep running even when the serving loop sits behind a slow
            # fsync (exactly the moments the monitor exists for).
            dur = getattr(self.state, "durability", None)
            if dur is not None:
                dur.start()
        self._blocker = _ReadbackBlocker()
        self._worker = threading.Thread(target=self._readback_thread,
                                        daemon=True,
                                        name="ocvf-readback-worker")
        self._worker.start()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ocvf-serve-loop")
        self._thread.start()

    def warmup(self) -> None:
        """Compile the serving + enrolment graphs before frames arrive, so
        the first batch and the first enroll command pay no compile stall.
        Every bucket of the dispatch ladder is compiled — a partial batch
        at any ladder size must never hit a mid-serving XLA compile."""
        t0 = time.perf_counter()
        prewarm = getattr(self.pipeline, "prewarm_batch_shapes", None)
        if prewarm is not None:
            prewarm(self._bucket_ladder, self.batcher.frame_shape,
                    self.batcher.dtype)
        else:
            # Pipelines without the helper (e.g. TwoStagePipeline) still
            # get every ladder size executed once.
            for bucket in self._bucket_ladder:
                zeros = np.zeros((bucket, *self.batcher.frame_shape),
                                 self.batcher.dtype)
                out = self.pipeline.recognize_batch_packed(zeros)
                if hasattr(out, "block_until_ready"):
                    out.block_until_ready()  # ocvf-lint: boundary=host-sync -- warmup precedes start(): blocking until every ladder bucket is compiled is the contract
        chunk = np.zeros((self._enrol_chunk, *self.pipeline.face_size), np.float32)
        emb = self._run_embed_chunk(self.pipeline.embed_params, chunk)
        if hasattr(emb, "block_until_ready"):
            emb.block_until_ready()  # ocvf-lint: boundary=host-sync -- warmup precedes start(); the enrolment graph must be compiled before the first enroll command
        self.metrics.observe(mn.WARMUP, time.perf_counter() - t0)
        # Arm the recompile watchdog: from here on, a serving dispatch
        # that misses the jit cache is a mid-serving XLA compile the
        # prewarmed ladder was built to prevent.
        self._warmed = True

    def drain(self, timeout: float = 120.0) -> bool:
        """Block until every accepted frame has been batched, computed, AND
        published (or timeout). Call at end-of-stream BEFORE stop() —
        stop() tears the loop down promptly and discards whatever is still
        queued, which is right for Ctrl-C but wrong for a finite stream.
        Event-driven against the completion condition variable; the wait
        tick only bounds how often the batcher's pending count re-checks."""
        deadline = time.monotonic() + timeout
        with self._inflight_cv:
            while time.monotonic() < deadline:
                # Ingest idle FIRST: a decode worker counts busy until
                # its sink (the batcher put) returns, so once idle reads
                # True no frame can still be in transit toward the
                # batcher checks below. delivered == completed covers
                # popped-but-undispatched batches, the in-flight queue,
                # AND publish-in-progress (completed is bumped only
                # after _publish returns).
                if ((self.ingest is None or self.ingest.idle())
                        and self.batcher.pending == 0
                        and self.batcher.delivered_batches == self._completed_batches):
                    return True
                self._inflight_cv.wait(timeout=LIVENESS_TICK_S)
        return False

    def stop(self) -> None:
        self._running = False
        self._flush_rejections(force=True)
        if self.state is not None:
            dur = getattr(self.state, "durability", None)
            if dur is not None:
                dur.stop()
        if self.ingest is not None:
            self.ingest.stop()
        self.batcher.close()
        with self._inflight_cv:
            self._inflight_cv.notify_all()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=5.0)
            self._thread = None
        worker = self._worker
        if worker is not None:
            # The worker finishes the remaining in-flight batches itself
            # (each wait bounded by that batch's readback deadline), then
            # exits; a worker still alive after the join is bounded-waiting
            # on a deadline and will finish its own drain.
            worker.join(timeout=5.0)
            self._worker = None
        if self._faults is not None and getattr(
                self.pipeline, "fault_injector", None) is self._faults:
            self.pipeline.fault_injector = None
        self.connector.stop()

    # ---- the serving loop ----

    @property
    def loop_crashed(self) -> bool:
        """True when an exception escaped a serving-side thread (the
        dispatch loop or the readback worker) and killed it
        (``ServiceSupervisor`` watches this flag)."""
        return self._crashed

    @property
    def loop_staleness_s(self) -> float:
        """Seconds since the serving loop last completed a queue pop —
        the loop_liveness gauge SLO's probe (``runtime.slo``). 0.0 while
        the service is stopped or the loop has not reached its first
        iteration yet (startup is covered by the bounded backend probe,
        not this signal)."""
        if not self._running or self._loop_progress_t is None:
            return 0.0
        return max(0.0, time.monotonic() - self._loop_progress_t)

    def restart_pending(self) -> bool:
        """True when the crash flag is up AND a serving-side thread has
        actually exited — i.e. ``restart_loop`` would act rather than
        no-op. The supervisor polls this instead of inspecting threads:
        a flag raised while the thread is still unwinding (slow 'crashed'
        status subscriber) must not burn phantom restarts."""
        if not self._crashed or not self._running:
            return False
        if self._thread is not None and not self._thread.is_alive():
            return True
        return self._worker is not None and not self._worker.is_alive()

    def restart_loop(self) -> None:
        """Restart crashed serving-side threads (supervisor path): whichever
        of the dispatch loop / readback worker died is respawned; a thread
        still alive is left untouched. Batch accounting needs no resync —
        every crash path settles its own popped batch before propagating
        (see ``_serve_one`` / ``_readback_loop``)."""
        if not self._running or self._thread is None:
            return
        serve_dead = not self._thread.is_alive()
        worker_dead = (self._worker is not None
                       and not self._worker.is_alive())
        if not serve_dead and not worker_dead:
            return  # not actually crashed
        self._crashed = False
        if worker_dead:
            self._blocker = _ReadbackBlocker()
            self._worker = threading.Thread(target=self._readback_thread,
                                            daemon=True,
                                            name="ocvf-readback-worker")
            self._worker.start()
        if serve_dead:
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="ocvf-serve-loop")
            self._thread.start()

    def _loop(self) -> None:
        try:
            self._serve_loop()
        except Exception:  # noqa: BLE001 — flag the crash for the supervisor
            logging.getLogger(__name__).exception("serving loop crashed")
            self.metrics.incr(mn.LOOP_CRASHES)
            self._crashed = True
            self._publish_status({"status": "crashed"})

    def _pop(self, block: bool):
        """``get_batch`` under the ``pop_wait`` leaf: the batch (None
        when nothing is flushable) and the call's two instants."""
        t_pop = time.monotonic()
        with (tracing.annotation("pop_wait") if self.tracer is not None
              else tracing.NULL_SPAN):
            batch = self.batcher.get_batch(block=block)
        t_popped = time.monotonic()
        busy = self._loop_busy
        busy["pop_wait"] = busy.get("pop_wait", 0.0) + t_popped - t_pop
        return batch, t_pop, t_popped

    def _serve_loop(self) -> None:
        self._loop_busy.clear()  # a crashed predecessor's half iteration
        # An iteration runs from the end of the one before to the end of
        # the batch it serves; idle ticks in between belong to it.
        t_iter = time.monotonic()
        c_iter = time.thread_time()
        # A batch the loop already holds (``_ahead``: its gate went to the
        # device ahead of the step before it) is served before anything
        # is popped, after stop() and after a supervisor's restart too:
        # the batcher has counted it delivered.
        while self._running or self._ahead is not None:
            held, self._ahead = self._ahead, None
            batch = None
            if held is None:
                batch, t_pop, t_popped = self._pop(block=True)
            # Liveness stamp: placed AFTER the pop so a loop wedged
            # anywhere in the iteration body (dispatch, inflight wait,
            # publish) stops refreshing it and ``loop_staleness_s`` grows.
            self._loop_progress_t = time.monotonic()
            # Durable-state tick: a cheap WAL row-count/age threshold
            # check; when due it SPAWNS the checkpoint worker (snapshot +
            # write happen off-thread, single-flight) — dispatch never
            # blocks on a checkpoint.
            if self.state is not None:
                self.state.tick()
                # Degraded-durability tick: interval-gated disk watermark
                # refresh ONLY (probe=False by default — the recovery
                # probe is a blocking fsync against a disk known broken,
                # and it belongs to the monitor's background thread, not
                # this loop). The non-due path is one clock read.
                dur = getattr(self.state, "durability", None)
                if dur is not None:
                    dur.tick()
            # SLO tick: one clock read when not due; a full burn-rate
            # evaluation every interval_s (runtime.slo). Runs on batch
            # AND idle iterations so the health verdict keeps updating
            # when traffic stops — recovery is part of the signal.
            if self.slo is not None:
                self.slo.tick()
            # Read-replica tick: tail the shared WAL and apply new rows
            # between batches (interval-gated inside poll; the non-due
            # path is one clock read). A poll failure (disk blip on the
            # shared dir) must cost this poll, never the serving loop —
            # the lag gauges and SLO objective surface a replica that
            # stops advancing.
            if self.replica is not None:
                try:
                    self.replica.poll()
                except Exception:  # noqa: BLE001 — replication must not kill serving
                    logging.getLogger(__name__).exception(
                        "read-replica WAL poll failed")
                    self.metrics.incr(mn.REPLICATION_POLL_ERRORS)
            if held is None:
                if batch is None:
                    if not self._running:
                        break
                    # Idle tick: an empty queue means zero queue wait — feed
                    # the brownout EWMA so it recovers even when the flood
                    # stops dead (no batches would otherwise update it) — and
                    # announce any rejections still pending from a flood that
                    # ended mid-aggregation-window.
                    self._note_queue_wait(0.0)
                    self._flush_rejections()
                    continue
                held = self._open_batch(batch, t_pop, t_popped)
            self._serve_one(held)
            c_end = time.thread_time()
            t_end = time.monotonic()
            self._flush_loop_busy(t_end - t_iter, c_end - c_iter)
            t_iter, c_iter = t_end, c_end

    def _open_batch(self, batch, t_pop: float, t_popped: float) -> _Held:
        """A popped batch's way through the loop up to its gate's
        enqueue: queue-wait observations, the brownout trim, the
        track-cache lookups (hits leave the staging buffer's front) and
        stage 1 put on the device. Nothing is read back and nothing is
        published, so the loop runs this for batch n+1 ahead of step n's
        enqueue when a closed batch is already waiting. A crash settles
        the batch before it propagates."""
        trace_ids = batch.trace_ids
        tracer = self.tracer
        # Batch trace: the coalescing ancestor every traced frame in this
        # batch points at (queue_wait spans carry ``batch=<this id>``);
        # batch-level spans (dispatch/ready_wait/publish) ride it. Never
        # sampled independently — it exists iff any member frame is traced.
        batch_tid = (tracer.new_trace()
                     if tracer is not None and any(trace_ids) else 0)
        t0 = now_mono = time.monotonic()
        # ``dispatch`` runs from here to the step's enqueue, through three
        # exits and, for a batch opened ahead, around the step before it:
        # it cannot be one ``with`` block, so its id is drawn now for the
        # leaves under it to name as ``parent``.
        disp_id = self._dispatch_span = (tracer.new_span_id()
                                         if batch_tid else 0)
        if batch_tid:
            # Before ``dispatch``, a root of the same trace: the
            # ``get_batch`` call that returned this batch.
            tracer.emit(batch_tid, "pop_wait", topic=tracing.BATCH_TOPIC,
                        t0=t_pop, dur=t_popped - t_pop)
        # Queue-wait: frame enqueue -> batch pop. The batching-delay
        # term of the end-to-end latency decomposition (continuous-batching
        # deadline + waiting for batch_size peers), measured per frame —
        # and the brownout controller's load signal (batch mean).
        for ts, tid in zip(batch.enqueue_ts, trace_ids):
            self.metrics.observe(mn.QUEUE_WAIT, now_mono - ts)
            if tid:
                tracer.emit(tid, "queue_wait", topic=FRAME_TOPIC, t0=ts,
                            dur=now_mono - ts, batch=batch_tid)
        if batch.enqueue_ts:
            self._note_queue_wait(
                sum(now_mono - ts for ts in batch.enqueue_ts)
                / len(batch.enqueue_ts))
        # Max-brownout ladder cap: trim an oversized batch down to one
        # small fast device call; the trimmed (newest) frames are shed
        # with an explicit reason, not silently truncated.
        cap = self._brownout_bucket_cap()
        if cap is not None and batch.count > cap:
            count = batch.count
            self.metrics.incr(mn.FRAMES_DROPPED_BROWNOUT, count - cap)
            self._trace_settle(trace_ids[cap:count],
                               mn.FRAMES_DROPPED_BROWNOUT,
                               "dispatch.brownout_trim", batch=batch_tid)
            self._journal_drop("brownout", self._drop_entries(
                batch.metas[cap:count], batch.enqueue_ts[cap:count],
                trace_ids[cap:count], "dispatch.brownout_trim"),
                level=self._brownout_level)
            batch = batch._replace(count=cap)
        held = _Held(batch, batch_tid, disp_id, t0)
        try:
            # Track-cache gate (ISSUE 17), BEFORE the cascade: a lookup
            # is pure host work, cheaper than the stage-1 device pass, so
            # cache hits save both stages. Hits settle as
            # ``completed_cached`` (published with the cached identities,
            # never dispatched); the survivors compact toward the staging
            # buffer's front exactly like the cascade's, so the rungs
            # below dispatch only what actually needs device work.
            if batch.count and self.tracker is not None:
                self._track_lookups(held)
            # Stage-1 cascade gate (ISSUE 13): score what the cache left,
            # at its ladder rung.
            if held.batch.count and self._cascade_active:
                self._gate_enqueue(batch.frames, held.batch.count, batch_tid)
        except BaseException:
            self._settle_crashed(held)
            raise
        return held

    def _track_lookups(self, held: _Held) -> None:
        """Consult the track cache for every frame of the batch; hits go
        to ``held.cached`` and leave the staging buffer's front."""
        batch, batch_tid = held.batch, held.batch_tid
        frames, metas = batch.frames, batch.metas
        held.tracked = any(self._track_stream_key(metas[i]) is not None
                           for i in range(batch.count))
        with self._leaf("track_cache", batch_tid,
                        frames=batch.count) as span:
            stretch = self._track_reverify_stretch()
            track_ver = getattr(self.pipeline.gallery,
                                "embedder_version", None)
            if track_ver is not None:
                track_ver = int(track_ver)
            # Full registry stamp when the registry is wired: a
            # detector/cascade cutover invalidates cached verdicts
            # exactly like an embedder cutover (opaque equality).
            track_ver = self._model_stamp(track_ver)
            cached, keep_list = [], []
            for i in range(batch.count):
                hit = self._track_lookup(metas[i], frames[i],
                                         track_ver, stretch)
                if hit is not None:
                    cached.append((metas[i], batch.enqueue_ts[i],
                                   batch.trace_ids[i], batch.priorities[i],
                                   hit))
                else:
                    keep_list.append(i)
            span.attrs["hits"] = len(cached)
        if cached:
            # The hits leave ``count`` and join ``held.cached`` together:
            # a crash on either side settles each frame exactly once.
            with self._leaf("compact", batch_tid, kept=len(keep_list)):
                self._compact(held, np.asarray(keep_list, dtype=np.intp))
                held.cached = cached

    @staticmethod
    def _compact(held: _Held, keep_idx) -> None:
        """Survivors to the staging buffer's front, in place, and the
        batch's lists cut to them: the bucket slice at dispatch then
        takes the smallest rung that fits what is left."""
        batch = held.batch
        frames, kept = batch.frames, len(keep_idx)
        if kept:
            # Fancy-index gather copies survivors out before the front
            # rows are overwritten: safe in-place compaction of the
            # pooled staging buffer.
            frames[:kept] = frames[keep_idx]
        held.batch = batch._replace(
            metas=([batch.metas[i] for i in keep_idx]
                   + [None] * (len(batch.metas) - kept)),
            count=kept,
            enqueue_ts=[batch.enqueue_ts[i] for i in keep_idx],
            trace_ids=[batch.trace_ids[i] for i in keep_idx],
            priorities=[batch.priorities[i] for i in keep_idx])

    def _settle_early(self, held: _Held) -> int:
        """Publish the early exits the batch gathered (track-cache hits,
        then gate rejections), each kind under a ``settle_early`` leaf;
        returns how many. The rows leave ``held`` first and are settled
        here whatever happens: a crash inside one kind's publish settles
        that kind's remainder itself (``_complete_cached`` /
        ``_complete_empty``), and the kind not yet tried lands in the
        crash bucket here."""
        kinds = [(self._complete_cached, "cache", held.cached),
                 (self._complete_empty, "gate", held.rejected)]
        held.cached, held.rejected = [], []
        settled = 0
        try:
            while kinds:
                complete, exit_name, rows = kinds.pop(0)
                if rows:
                    with self._leaf("settle_early", held.batch_tid,
                                    exit=exit_name, frames=len(rows)):
                        complete(rows, held.batch_tid)
                    settled += len(rows)
        except BaseException:
            for _complete, _exit_name, rows in kinds:
                self.metrics.incr(mn.FRAMES_DROPPED_CRASHED, len(rows))
                self._trace_settle([r[2] for r in rows],
                                   mn.FRAMES_DROPPED_CRASHED,
                                   "settle_early.crashed",
                                   batch=held.batch_tid)
            raise
        return settled

    def _settle_crashed(self, held: _Held) -> None:
        """The held batch dies with a crash of the loop; settle it so
        drain()'s delivered==completed stays solvable after the
        supervisor restarts the loop — its survivors and the early exits
        it had not published yet land in the ledger's crash bucket, not
        in limbo. The staging buffer is forfeited, not recycled: the
        crash may have left an async H2D read of it pending."""
        batch = held.batch
        lost = (list(batch.trace_ids[:batch.count])
                + [r[2] for r in held.cached]
                + [r[2] for r in held.rejected])
        held.cached, held.rejected = [], []
        self.metrics.incr(mn.FRAMES_DROPPED_CRASHED, len(lost))
        self._trace_settle(lost, mn.FRAMES_DROPPED_CRASHED,
                           "dispatch.crashed", batch=held.batch_tid)
        self.batcher.forfeit(batch.frames)
        self._mark_completed()

    def _serve_one(self, held: _Held) -> None:
        """Feed, then settle. Feed is all the chip waits on: the gate's
        scores read, survivors compacted, the next closed batch's gate
        put on the device if one is already waiting, then this batch's
        upload and step. Settle is what it does not wait on: the publish
        of the batch's early exits (track-cache hits, gate rejections),
        which follows the step's enqueue — or stands in for it when no
        frame survives."""
        tracer = self.tracer
        batch_tid, disp_id, t0 = held.batch_tid, held.disp_id, held.t0
        self._dispatch_span = disp_id
        frames = held.batch.frames
        accounted = False
        try:
            # Where a batch without survivors left: the cache, unless the
            # gate turns away the rest.
            exit_stage = "track_cache"
            # Settlement ordering keeps the crash handler exact:
            # ``count`` shrinks to the survivors and the rejected rows
            # join ``held`` together, BEFORE anything else can fail, so
            # a crash anywhere after still settles every frame exactly
            # once.
            if held.batch.count and self._cascade_active:
                keep = self._cascade_keep_mask(frames, held.batch.count,
                                               batch_tid)
                if keep is not None and not keep.all():
                    with self._leaf("compact", batch_tid,
                                    kept=int(keep.sum())):
                        batch = held.batch
                        rejected = [
                            (batch.metas[i], batch.enqueue_ts[i],
                             batch.trace_ids[i], batch.priorities[i])
                            for i in np.flatnonzero(~keep)]
                        self._compact(held, np.flatnonzero(keep))
                        held.rejected = rejected
                    exit_stage = "cascade"
                    if held.tracked:
                        self._note_gate_misses(held.rejected, batch_tid)
            batch = held.batch
            metas, count, trace_ids = batch.metas, batch.count, batch.trace_ids
            if not count:
                # No survivor (every frame answered from the cache, or
                # turned away at stage 1): no stage-2 dispatch at all, THE
                # early-exit win, and nothing to put ahead of the settle.
                # The dispatch span records the exit stage so PR 8
                # attribution stays honest.
                self._settle_early(held)
                self.metrics.incr(mn.CASCADE_BATCH_EXITS
                                  if exit_stage == "cascade"
                                  else mn.TRACK_BATCH_EXITS)
                if batch_tid:
                    tracer.emit(batch_tid, "dispatch",
                                topic=tracing.BATCH_TOPIC, t0=t0,
                                dur=time.monotonic() - t0, span_id=disp_id,
                                bucket=0, frames=0, exit=exit_stage,
                                brownout=self._brownout_level)
                accounted = True
                self._mark_completed()
                # Stage 1 never saw the buffer, or its scores readback
                # completed, which fences the buffer's H2D read: safe to
                # recycle.
                self.batcher.recycle(frames)
                self.batcher.report_service_time(time.monotonic() - t0)
                return
            # Bucketed dispatch: slice the padded staging array down to the
            # smallest warmed ladder size that fits the real frames — a
            # view, not a copy, so steady state allocates nothing.
            bucket = self._pick_bucket(count)
            view = frames[:bucket] if bucket < len(frames) else frames
            if batch_tid and self.ingest is not None:
                # Ingest provenance: which staging rung carried the batch
                # and which bucket it dispatches at (rung >= bucket; the
                # ring hands the smallest rung that fits).
                tracer.emit(batch_tid, "stage", topic=tracing.BATCH_TOPIC,
                            parent=disp_id, rung=len(frames), bucket=bucket,
                            frames=count)
            # Gate n+1 ahead of step n: a closed batch already waiting
            # has its stage 1 put on the device's queue before this step,
            # so its scores come back while the step runs and the next
            # step is enqueued behind this one — the chip never waits for
            # a readback. Never waited for: with nothing closed, the step
            # goes to the chip at once. Only while the chip is the slower
            # party (``CHIP_BOUND_SHARE``): where the loop is, the chip is
            # idle by now and waits for this very step, and the next
            # gate's enqueue would only stand in its way. Off for a batch
            # the tracker was consulted for: the lookups of batch n+1
            # have to precede its gate (hits leave before stage 1 scores
            # the rest) and to follow this batch's publishes, which
            # follow the step.
            if (self._running and self._cascade_active
                    and not held.tracked
                    and self._chip_wait_s
                    >= CHIP_BOUND_SHARE * self._iteration_s):
                nxt, t_pop, t_popped = self._pop(block=False)
                if nxt is not None:
                    self._ahead = self._open_batch(nxt, t_pop, t_popped)
                    self.metrics.incr(mn.BATCHES_GATED_AHEAD)
                    self._dispatch_span = disp_id
            # Embedder-version stamp captured AT DISPATCH: the batch's
            # scores are computed against the gallery data this dispatch
            # reads, so its published results carry the version serving
            # when the batch entered the device — a cutover swapping the
            # gallery later never back-stamps an in-flight batch. (The
            # version moves monotonically and exactly once per rollout,
            # so per-replica result stamps form a clean old->new prefix —
            # the no-mixed-scores assertion chaos_soak checks.)
            gallery_ver = getattr(self.pipeline.gallery,
                                  "embedder_version", None)
            if gallery_ver is not None:
                gallery_ver = int(gallery_ver)
            # Registry-wired services widen the dispatch stamp to the
            # full (role, version) tuple HERE, for the same reason: a
            # registry cutover landing while this batch is on device
            # must never back-stamp its results with the new model set.
            gallery_ver = self._model_stamp(gallery_ver)
            packed = self._dispatch_with_retry(view, batch_tid)
            if packed is None:
                # Retries exhausted or the error was permanent (poisoned
                # batch): abandoned, not published — but still completed
                # for drain() accounting (and an explicit per-frame drop
                # in the admission ledger + journal). Its early exits
                # have their answers all the same, and get them first.
                self._settle_early(held)
                self.metrics.incr(mn.FRAMES_FAILED, count)
                self._trace_settle(trace_ids[:count], mn.FRAMES_FAILED,
                                   "dispatch.abandoned", batch=batch_tid)
                self._journal_drop("failed", self._drop_entries(
                    metas[:count], batch.enqueue_ts[:count],
                    trace_ids[:count], "dispatch.abandoned"))
                self._mark_completed()
                accounted = True
                if self.ingest is not None:
                    # An attempt's explicit async upload may still hold a
                    # pending read of this staging buffer — forfeit (the
                    # ring heals) instead of recirculating it.
                    self.batcher.forfeit(frames)
                else:
                    self.batcher.recycle(frames)
                return
            # Host-side dispatch cost (H2D + trace-cache hit + async enqueue
            # — never device compute, which is async from here).
            t_disp = time.monotonic()
            self.metrics.observe(mn.DISPATCH, t_disp - t0)
            deadline = t_disp + self.resilience.readback_deadline_s
            with self._inflight_cv:
                self._inflight.append((packed, frames, metas, count,
                                       batch.enqueue_ts, t0, t_disp, deadline,
                                       trace_ids, batch_tid,
                                       batch.priorities, gallery_ver))
                accounted = True
                self._inflight_cv.notify_all()
        except BaseException:
            if not accounted:
                self._settle_crashed(held)
            raise
        self.metrics.incr(mn.BATCHES_DISPATCHED)
        self.metrics.incr(mn.FRAMES_PROCESSED, count)
        # Dispatch provenance is read for the batch span AND the recompile
        # watchdog, so it is fetched regardless of tracing.
        info = getattr(self.pipeline, "last_dispatch_info", None) or {}
        if info.get("embed_slots"):
            # the step's counts of work under one acquisition of the lock
            # (tokens stay 0 where the embedder has no token axis, the
            # kernel's slots where its attention did not lower to the kernel)
            self.metrics.incr_many(
                (mn.EMBED_SLOTS, info["embed_slots"]),
                (mn.DETECT_FRAMES, info.get("detect_frames", 0)),
                (mn.EMBED_TOKENS, info.get("embed_tokens", 0)),
                (mn.EMBED_ATTN_KERNEL_SLOTS,
                 info["embed_slots"] if info.get("embed_attention") == "kernel" else 0))
        if batch_tid:
            # Bucketed-dispatch provenance: bucket size, jit-cache verdict
            # and exact-vs-ivf matcher mode (the pipeline records both on
            # dispatch), plus the brownout level the batch served under
            # and the cascade exit stage (``full`` = stage 2 ran; a batch
            # that never got here carries ``exit="cascade"`` instead).
            tracer.emit(batch_tid, "dispatch", topic=tracing.BATCH_TOPIC,
                        t0=t0, dur=t_disp - t0, span_id=disp_id,
                        bucket=bucket, frames=count,
                        cache_hit=info.get("cache_hit"),
                        mode=info.get("mode"), exit="full",
                        detector=info.get("detector"),
                        embedder=info.get("embedder"),
                        attention=info.get("embed_attention"),
                        brownout=self._brownout_level)
        # What follows lies after ``dispatch``: roots of the batch trace.
        self._dispatch_span = 0
        if self._warmed and info.get("cache_hit") is False:
            # Recompile watchdog (see _note_recompile): a serving
            # dispatch missed the jit cache AFTER warmup compiled the
            # whole bucket ladder.
            self._note_recompile(bucket, count, info.get("mode"))
        if bucket < self.batcher.batch_size:
            self.metrics.incr(mn.BATCHES_BUCKETED)
        # The step is on the device's queue and the batch on ``_inflight``:
        # now the host work the chip does not wait on.
        deferred = self._settle_early(held)
        if deferred:
            self.metrics.incr(mn.EARLY_EXITS_DEFERRED, deferred)
        # Backpressure: beyond inflight_depth undrained batches, wait for
        # the readback worker to free a slot (it notifies the cv on every
        # pop) before popping more frames. The timeout only bounds
        # liveness re-checks (stop), never paces a healthy pipeline.
        # Deliberately NOT escaped on a worker crash: parking here keeps
        # the in-flight queue bounded until the supervisor respawns the
        # worker (or stop() clears _running).
        # The leaf closes (and its span is emitted) once the condition's
        # lock is released.
        with self._leaf("inflight_wait", batch_tid):
            with self._inflight_cv:
                while (self._running
                       and len(self._inflight) > self.inflight_depth):
                    self._inflight_cv.wait(timeout=LIVENESS_TICK_S)

    def _mark_completed(self, n: int = 1) -> None:
        with self._inflight_cv:
            self._completed_batches += n
            self._inflight_cv.notify_all()

    def _dispatch_with_retry(self, frames, batch_tid: int = 0
                             ) -> Optional[Any]:
        """One batch through the device, honoring the resilience policy:
        transient failures retry with exponential backoff (the readback
        worker keeps draining while we wait), permanent ones abandon
        immediately, and ``degraded_after`` consecutive failed attempts
        publish degraded mode. Returns the dispatched (async) output, or
        None when the batch is abandoned (``batches_failed``). With the
        ingest subsystem, every ATTEMPT re-uploads the host staging view
        explicitly (uint8 across the wire, cast fused on device) — a
        donated device buffer from a failed attempt is never re-fed."""
        policy = self.resilience
        attempt = 0
        while True:
            try:
                send = frames
                if self.ingest is not None:
                    with self._leaf("upload", batch_tid,
                                    dtype=str(frames.dtype)) as span:
                        send, up_bytes, _up_dur = self.ingest.upload(frames)
                        span.attrs["bytes"] = up_bytes
                # Packed path: ONE output array -> one D2H readback per
                # batch instead of five (see pipeline.pack_result). Without
                # an ingest subsystem the implicit host->device transfer
                # of ``send`` is part of this leaf.
                with self._leaf("step_enqueue", batch_tid):
                    packed = self.pipeline.recognize_batch_packed(send)
                    packed.copy_to_host_async()
            except Exception as exc:  # noqa: BLE001 — classified below
                self.metrics.incr(mn.DISPATCH_FAILURES)
                self._consecutive_dispatch_failures += 1
                if (self._consecutive_dispatch_failures >= policy.degraded_after
                        and not self._degraded):
                    self._enter_degraded(exc)
                transient = is_transient_error(exc)
                if not transient or attempt >= policy.dispatch_retries:
                    logging.getLogger(__name__).exception(
                        "recognition batch abandoned (%s, attempt %d)",
                        "transient" if transient else "permanent", attempt)
                    self.metrics.incr(mn.BATCHES_FAILED)
                    return None
                self.metrics.incr(mn.DISPATCH_RETRIES)
                self._backoff_wait(policy.backoff(attempt))
                attempt += 1
                if not self._running:
                    self.metrics.incr(mn.BATCHES_FAILED)
                    return None
                continue
            if self._consecutive_dispatch_failures:
                self._consecutive_dispatch_failures = 0
            if self._degraded:
                self._exit_degraded()
            # Async-readback fault boundary (runtime.faults): may wrap the
            # output in a never-ready proxy — the hang-mode outage.
            if self._faults is not None:
                packed = self._faults.on_readback(packed)
            return packed

    def _backoff_wait(self, seconds: float) -> None:
        """Sleep in small slices, bailing promptly on stop(). The readback
        worker keeps draining in-flight batches meanwhile."""
        deadline = time.monotonic() + seconds
        while self._running and time.monotonic() < deadline:
            time.sleep(min(0.01, max(0.0, deadline - time.monotonic())))

    # ---- degraded mode ----

    def _enter_degraded(self, exc: BaseException) -> None:
        self._degraded = True
        self.metrics.incr(mn.DEGRADED_TRANSITIONS)
        status = {
            "status": "degraded",
            "consecutive_failures": self._consecutive_dispatch_failures,
            "error": repr(exc),
        }
        if self.resilience.probe_backend_on_degraded:
            usable, reason = self._probe_backend()
            status["backend_usable"] = usable
            status["backend_reason"] = reason
            if not usable and self._cpu_fallback is not None:
                try:
                    self._cpu_fallback(self)
                    self.metrics.incr(mn.CPU_FALLBACKS)
                    status["cpu_fallback"] = True
                except Exception:  # noqa: BLE001 — fallback is best-effort
                    logging.getLogger(__name__).exception("cpu fallback failed")
                    status["cpu_fallback"] = False
        self._publish_status(status)

    def _exit_degraded(self) -> None:
        self._degraded = False
        self.metrics.incr(mn.DEGRADED_RECOVERIES)
        status = {"status": "recovered"}
        if self._embed_device is not None:
            # "Recovered" only in the sense that dispatches succeed again —
            # on the CPU-fallback pipeline, not the accelerator. Deploy
            # tooling must keep treating the job as degraded-capacity.
            status["on_cpu_fallback"] = True
        self._publish_status(status)

    def _publish_status(self, status: Dict[str, Any]) -> None:
        """Status publishes run on serving-side threads and subscribers are
        arbitrary app code — a raising status consumer must degrade to a
        logged error, never crash the loop it is reporting on."""
        try:
            self.connector.publish(STATUS_TOPIC, status)
        except Exception:  # noqa: BLE001 — transport/subscriber may be down
            logging.getLogger(__name__).exception("status publish failed")

    def _probe_backend(self) -> tuple:
        """Bounded ``(usable, reason)`` verdict on the accelerator (never
        hangs): the injected fn for tests, else ``probe_device`` on the
        first device of the gallery's mesh — the device this process
        already holds, so the verdict is about the chip serving runs on
        (a child process could not even open it)."""
        if self._backend_probe_fn is not None:
            return self._backend_probe_fn()
        device = self.pipeline.gallery.mesh.devices.flat[0]
        return probe_device(device, self.resilience.probe_timeout_s)

    def _dead_letter(self, count: int, metas: Optional[List[Any]] = None,
                     enqueue_ts: Optional[List[float]] = None,
                     trace_ids: Optional[List[int]] = None,
                     batch: int = 0) -> None:
        """Abandon a batch whose readback outlived its deadline: counted,
        announced, completed — never blocked on (SURVEY.md §5.3: an
        unhealthy accelerator degrades the job, never wedges it). The
        status message carries the dead frames' ids (their ``meta``) and
        enqueue timestamps so producers can retry, and the same entries
        land in the dead-letter journal. A dead-letter is also a
        flight-recorder trigger: the span rings are dumped (rate-limited)
        and the dump path rides the journal record, so "what was in
        flight when this batch died" is answerable after the fact."""
        self.metrics.incr(mn.BATCHES_DEAD_LETTERED)
        self.metrics.incr(mn.FRAMES_DEAD_LETTERED, count)
        self._mark_completed()
        # Slice every provenance list to ``count``: metas is the PADDED
        # [batch_size] list, and after a brownout trim the enqueue_ts/
        # trace_ids lists still hold the trimmed (already settled) frames
        # beyond count — journaling or re-settling those would invent
        # phantom rows / duplicate terminal spans.
        metas = (list(metas[:count]) if metas is not None
                 else [None] * count)
        enqueue_ts = enqueue_ts[:count] if enqueue_ts is not None else None
        trace_ids = trace_ids[:count] if trace_ids is not None else None
        self._trace_settle(trace_ids if trace_ids is not None else (),
                           mn.FRAMES_DEAD_LETTERED, "readback.dead_letter",
                           batch=batch)
        dump = None
        if self.tracer is not None:
            if batch:
                self.tracer.emit(batch, "dead_letter",
                                 topic=tracing.BATCH_TOPIC, frames=count)
            dump = self.tracer.dump("dead_letter",
                                    extra={"frames": count,
                                           "ledger": self.ledger()})
        entries = self._drop_entries(metas, enqueue_ts, trace_ids,
                                     "readback.dead_letter")
        extra = {"dump": dump} if dump else {}
        self._journal_drop("dead_letter", entries, **extra)
        self._publish_status({
            "status": "dead_letter",
            "frames": count,
            "frame_ids": [e["meta"] for e in entries],
            "enqueued_at": [e["enqueue_ts"] for e in entries],
        })

    @staticmethod
    def _is_ready(packed) -> bool:
        """Non-blocking readiness; backends without ``is_ready`` report
        ready and fall back to the blocking materialize (old behavior).
        A RAISING is_ready (outage surfacing at the readback side) also
        reports ready: the materialize then surfaces the error where
        ``_complete_head`` dead-letters it instead of crashing a thread."""
        try:
            return bool(packed.is_ready())
        except (AttributeError, NotImplementedError):
            return True
        except Exception:  # ocvf-lint: disable=swallowed-exception -- deliberate defer: reporting ready makes materialize re-raise on the classifying path, where _complete_head dead-letters with full accounting
            return True

    # ---- the readback worker ----

    def _readback_thread(self) -> None:
        try:
            self._readback_loop()
        except Exception:  # noqa: BLE001 — flag the crash for the supervisor
            logging.getLogger(__name__).exception("readback worker crashed")
            self.metrics.incr(mn.LOOP_CRASHES)
            self._crashed = True
            self._publish_status({"status": "crashed"})

    def _readback_loop(self) -> None:
        """Drain the in-flight queue in dispatch order: block on each
        batch's device array (bounded by its readback deadline), then
        materialize + publish. Runs until stopped AND the queue is empty,
        so stop() after drain() loses nothing. The entry stays at the head
        of the deque while we wait — the backpressure slot is only freed
        (cv notified) once its batch's device round-trip actually ended."""
        self._publish_cpu_mark = None  # a new thread's CPU clock starts anew
        while True:
            with self._inflight_cv:
                while self._running and not self._inflight:
                    self._inflight_cv.wait(timeout=LIVENESS_TICK_S)
                if not self._inflight:
                    if not self._running:
                        return
                    continue
                packed, frames, metas, count, enqueue_ts, t0, t_disp, \
                    deadline, trace_ids, batch_tid, priorities, \
                    gallery_ver = self._inflight[0]
            try:
                ready = self._await_ready(packed, deadline)
            except Exception:  # noqa: BLE001 — outage at the readback side
                # A transient backend error surfacing here must cost this
                # batch, not the worker thread (a crash loop would burn
                # the supervisor's bounded restarts on an outage the
                # dispatch side survives via retry/degraded mode).
                logging.getLogger(__name__).exception("readback wait failed")
                self.metrics.incr(mn.READBACK_ERRORS)
                ready = False
            with self._inflight_cv:
                self._inflight.popleft()
                self._inflight_cv.notify_all()
            if not ready:
                # Do NOT recycle the staging buffer: the batch's device
                # round-trip never completed, so the backend's async H2D
                # read of this exact host array may still be pending —
                # reusing it would race the outage we just survived. The
                # legacy pool refills from completed batches; a bounded
                # staging ring is told explicitly (forfeit) so it may
                # heal with one replacement allocation.
                self.batcher.forfeit(frames)
                self._dead_letter(count, metas, enqueue_ts, trace_ids,
                                  batch_tid)
                continue
            self._complete_head(packed, frames, metas, count, enqueue_ts,
                                t0, t_disp, trace_ids, batch_tid, priorities,
                                gallery_ver)

    def _await_ready(self, packed, deadline: float) -> bool:
        """Wait for one batch's transfer, bounded by its deadline. Returns
        False when the deadline won (caller dead-letters). Event-driven:
        the sacrificial blocker thread performs ``block_until_ready`` so a
        hang-mode outage costs one abandoned daemon thread, not a wedged
        worker — and a healthy readback never pays an ``is_ready`` poll
        interval."""
        if not hasattr(packed, "block_until_ready"):
            return True  # plain host value (already materialized)
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return self._is_ready(packed)
        blocker = self._blocker
        if blocker is None:
            blocker = self._blocker = _ReadbackBlocker()
        outcome = blocker.block(packed, remaining)
        if outcome == "ready":
            return True
        if outcome == "timeout":
            # The blocker may be wedged in native code on the hung array —
            # abandon it; the next batch gets a fresh one.
            self._blocker = _ReadbackBlocker()
            return False
        # "raised": either a proxy that refuses to block (the injected
        # stuck readback raises instead of hanging the suite) or a failed
        # computation (ready-with-error). Bounded is_ready polling sorts
        # them out: never-ready dead-letters at the deadline; a failed
        # computation reports ready and materializes its error upstream.
        while self._running and time.monotonic() < deadline:
            if self._is_ready(packed):
                return True
            time.sleep(READY_POLL_S)
        return self._is_ready(packed)

    def _complete_head(self, packed, frames, metas, count, enqueue_ts,
                       t0, t_disp, trace_ids=(), batch_tid=0,
                       priorities=(), gallery_ver=None) -> None:
        """Materialize + publish one POPPED batch and settle its accounting
        (the readback worker's tail).

        Three invariants live here:
        - a materialize failure (an outage error riding the result array)
          dead-letters the batch (``readback_errors``) instead of crashing
          the thread — the readback-side mirror of the dispatch retry
          classification;
        - ``ready_wait`` ends AFTER ``np.asarray``: whatever of the
          readback the conversion still does lands in this term and must
          never leak into 'publish';
        - a crash escaping the publish path still settles
          ``_completed_batches`` first, so drain() stays solvable after
          the supervisor restarts the thread.
        """
        try:
            arr = np.asarray(packed)  # ocvf-lint: boundary=host-sync -- THE one per-batch materialize (PR 2's packed single-readback design); runs on the readback worker, never ahead of readiness
        except Exception:  # noqa: BLE001 — outage error carried by the array
            logging.getLogger(__name__).exception(
                "readback materialize failed")
            self.metrics.incr(mn.READBACK_ERRORS)
            # completed++, no recycle (see above); forfeit so a ring heals
            self.batcher.forfeit(frames)
            self._dead_letter(count, metas, enqueue_ts, trace_ids, batch_tid)
            return
        t_pub = time.monotonic()
        self.metrics.observe(mn.READY_WAIT, t_pub - t_disp)
        if batch_tid:
            # Dispatch -> readback-complete: the device round-trip term,
            # from the instant ``dispatch`` ended.
            self.tracer.emit(batch_tid, "ready_wait",
                             topic=tracing.BATCH_TOPIC, t0=t_disp,
                             dur=t_pub - t_disp, frames=count)
        try:
            with (self.tracer.span(batch_tid, "publish", frames=count)
                  if batch_tid else tracing.NULL_SPAN) as span:
                self._publish(arr, frames, metas, count, trace_ids,
                              batch_tid, gallery_ver, publish_span=span.id)
        except BaseException:
            self._mark_completed()
            # The readback COMPLETED before publish, so the staging
            # buffer is safe to recirculate — and with a bounded ring it
            # MUST be: dropping it here would shrink the ring by one per
            # publish crash with no heal credit, until admission sheds
            # everything against a ring that can never refill.
            self.batcher.recycle(frames)
            raise
        self._mark_completed()
        now = time.monotonic()
        self.metrics.observe(mn.PUBLISH, now - t_pub)
        self.metrics.observe(mn.BATCH_LATENCY, now - t0)
        # Per-frame end-to-end latency (batcher enqueue -> published):
        # the SLO layer's headline histogram, split by priority class so
        # the interactive objective never averages in bulk traffic.
        # enqueue_ts stamps are monotonic; one clock read covers the run.
        if enqueue_ts:
            for i in range(min(count, len(enqueue_ts))):
                self._observe_e2e(
                    enqueue_ts[i],
                    priorities[i] if i < len(priorities)
                    else PRIORITY_INTERACTIVE + 1,
                    now)
        # Feed the continuous batcher's adaptive deadline with the
        # realized downstream time (pop -> published).
        self.batcher.report_service_time(now - t0)
        self.batcher.recycle(frames)

    def _publish(self, packed, frames, metas, count, trace_ids=(),
                 batch_tid=0, gallery_ver=None, publish_span=0) -> None:
        from opencv_facerecognizer_tpu.parallel.pipeline import unpack_result

        t_pub = time.monotonic()
        c_pub = time.thread_time()
        published = 0
        # ``tracker.update``: seconds and calls of this batch, counted
        # once below; a span (child of ``publish_span``) per sampled frame.
        track_s, track_n = 0.0, 0
        tracer = self.tracer
        rollout = self.rollout
        registry_swap = self.registry_swap
        # ``gallery_ver`` is the DISPATCH-time model stamp: a plain int
        # embedder version, or the full registry (role, version) tuple
        # when the registry is wired. Split once — every published row
        # and tracker verdict in this batch carries the same stamp, so a
        # cutover landing mid-publish never splits a batch.
        stamp = gallery_ver
        emb_ver, reg_roles = self._stamp_fields(stamp)
        try:
            result = unpack_result(np.asarray(packed), self.pipeline.top_k)  # no-op if already host
            boxes = result.boxes
            det_scores = result.det_scores
            valid = result.valid
            labels = result.labels
            sims = result.similarities
            for i in range(count):
                faces = []
                for j in range(boxes.shape[1]):
                    if not valid[i, j]:
                        continue
                    sim = float(sims[i, j, 0])
                    label = int(labels[i, j, 0])
                    known = sim >= self.similarity_threshold and label >= 0
                    name = (
                        self.subject_names[label]
                        if known and label < len(self.subject_names)
                        else ("unknown" if not known else str(label))
                    )
                    y0, x0, y1, x1 = (float(v) for v in boxes[i, j])
                    faces.append({
                        "box": [x0, y0, x1, y1],  # x-first, like the reference API
                        "detection_score": float(det_scores[i, j]),
                        "label": label if known else -1,
                        "name": name,
                        "similarity": sim,
                    })
                self._maybe_collect_enrolment(frames[i], faces)
                payload = {"meta": metas[i], "faces": faces}
                if emb_ver is not None:
                    # The embedder version the batch was SCORED against
                    # (captured + int-coerced at dispatch) — consumers and
                    # the rollout chaos scenario key the no-mixed-scores
                    # invariant on this stamp.
                    payload["embedder_version"] = emb_ver
                if reg_roles is not None:
                    # The full registry stamp (dispatch-time): the chaos
                    # registry scenario keys its no-unfenced-version
                    # assertion on this dict.
                    payload["registry"] = reg_roles
                self.connector.publish(RESULT_TOPIC, payload)
                published += 1
                self.metrics.incr(mn.FACES_FOUND, len(faces))
                if self.tracker is not None:
                    # Every FULL published result re-verifies its
                    # stream's tracks (association + identity
                    # cross-check + miss aging). Fail open: a tracker
                    # bug costs future cache wins, never this result.
                    key = self._track_stream_key(metas[i])
                    if key is not None:
                        tid = (trace_ids[i] if batch_tid
                               and i < len(trace_ids) else 0)
                        t_track = time.monotonic()
                        try:
                            with (tracer.span(batch_tid, "track_update",
                                              parent=publish_span, frame=tid)
                                  if tid else tracing.NULL_SPAN):
                                self.tracker.update(
                                    key, faces, frames[i],
                                    embedder_version=stamp)
                        except Exception:  # noqa: BLE001 — cache only
                            logging.getLogger(__name__).exception(
                                "tracker update failed")
                            self.metrics.incr(mn.TRACK_ERRORS)
                        track_s += time.monotonic() - t_track
                        track_n += 1
                if rollout is not None and faces:
                    # Dual-score parity sampling (rate-limited + copied
                    # inside; scored on the rollout thread). A coordinator
                    # bug must cost a counter, never the publish path.
                    try:
                        rollout.offer_live(frames[i], faces)
                    except Exception:  # noqa: BLE001 — observation only
                        logging.getLogger(__name__).exception(
                            "rollout live-parity offer failed")
                        self.metrics.incr(mn.ROLLOUT_OBSERVE_ERRORS)
                if registry_swap is not None:
                    # Detection-parity sampling for an in-flight registry
                    # swap: whole frames + the serving detector's verdict
                    # boxes (the publish path already paid for them), so
                    # the candidate detector is scored against live
                    # traffic including face-free frames. Same fail-open
                    # contract as the rollout offer.
                    try:
                        registry_swap.offer_live(frames[i], faces)
                    except Exception:  # noqa: BLE001 — observation only
                        logging.getLogger(__name__).exception(
                            "registry live-parity offer failed")
                        self.metrics.incr(mn.REGISTRY_OBSERVE_ERRORS)
        finally:
            # Ledger settlement happens HERE, per batch, whatever exits:
            # frames that made it out are completed; on a crash escaping
            # mid-batch the remainder lands in the crash bucket (the
            # publishing thread dies, the supervisor restarts it — the
            # frames must not stay in limbo between those events). The
            # terminal spans mirror the same split exactly.
            self.metrics.incr(mn.FRAMES_COMPLETED, published)
            if track_n:
                self.metrics.incr(mn.PUBLISH_S_TRACK_UPDATE, track_s)
                self.metrics.incr(mn.TRACK_UPDATES, track_n)
            self._trace_settle(trace_ids[:published],
                               tracing.OUTCOME_COMPLETED, "publish",
                               batch=batch_tid)
            if published < count:
                self.metrics.incr(mn.FRAMES_DROPPED_CRASHED, count - published)
                self._trace_settle(trace_ids[published:count],
                                   mn.FRAMES_DROPPED_CRASHED,
                                   "publish.crashed", batch=batch_tid)
            # Busy time of publishing, beside its count (frames_completed
            # above): the whole of this method, settle spans included, by
            # the wall clock and by this thread's CPU clock (two reads a
            # batch). ``readback_cpu_s`` runs from one batch's second read
            # to the next's: the thread's CPU as a whole, so less
            # ``publish_cpu_s`` it is what the worker ran outside this
            # method (the materialize, the latency observations, the
            # recycle), which no wall counter covers.
            c_end = time.thread_time()
            wall = time.monotonic() - t_pub
            mark, self._publish_cpu_mark = self._publish_cpu_mark, c_end
            self.metrics.incr_many(
                (mn.PUBLISH_S, wall), (mn.PUBLISH_CPU_S, c_end - c_pub),
                (mn.READBACK_CPU_S, c_end - (c_pub if mark is None else mark)))

    # ---- enrolment (interactive-trainer protocol) ----

    def _maybe_collect_enrolment(self, frame: np.ndarray, faces: List[dict]) -> None:
        with self._enrol_lock:
            enrolment = self._enrolment
        if enrolment is None or not faces:
            return
        best = max(faces, key=lambda f: f["detection_score"])
        x0, y0, x1, y1 = (int(round(v)) for v in best["box"])
        h, w = frame.shape
        y0, y1 = max(0, y0), min(h, y1)
        x0, x1 = max(0, x0), min(w, x1)
        if y1 - y0 < 4 or x1 - x0 < 4:
            return
        # COPY, not a view: the frame lives in a pooled staging buffer that
        # is recycled (and overwritten) as soon as this batch completes.
        enrolment.crops.append(frame[y0:y1, x0:x1].copy())
        if len(enrolment.crops) >= enrolment.needed:
            with self._enrol_lock:
                self._enrolment = None
            # Off the serving threads: the embed + gallery install must not
            # stall frame batches (reload-without-drop, SURVEY.md §5.3).
            threading.Thread(
                target=self._finish_enrolment, args=(enrolment,), daemon=True
            ).start()

    def _finish_enrolment(self, enrolment: _Enrolment) -> None:
        from opencv_facerecognizer_tpu.ops import image as image_ops

        face_size = self.pipeline.face_size
        # Version fence stamp, read BEFORE the embed: these crops are
        # about to be embedded by the CURRENT model — if a rollout
        # cutover swaps the space before the WAL append below, the
        # lifecycle refuses the stale-space rows closed
        # (EmbedderVersionMismatchError) instead of mixing them in.
        enrol_version = getattr(self.pipeline.gallery, "embedder_version",
                                None)
        crops = np.stack(
            [np.asarray(image_ops.resize(c, face_size)) for c in enrolment.crops]  # ocvf-lint: boundary=host-sync -- enrolment readback: _finish_enrolment runs on its own daemon thread, off the serving loop by design
        )
        # Embed in fixed-size padded chunks (pre-compiled in warmup()).
        embeddings = []
        for start in range(0, len(crops), self._enrol_chunk):
            part = crops[start : start + self._enrol_chunk]
            padded = np.zeros((self._enrol_chunk, *face_size), np.float32)
            padded[: len(part)] = part
            emb = np.array(self._run_embed_chunk(self.pipeline.embed_params,  # ocvf-lint: boundary=host-sync -- enrolment embed readback on the dedicated enrolment thread; frame batches keep flowing while this blocks
                                                 padded))
            embeddings.append(emb[: len(part)])
        emb = np.concatenate(embeddings)
        with self._enrol_lock:
            if enrolment.subject_name in self.subject_names:
                label = self.subject_names.index(enrolment.subject_name)
            else:
                label = len(self.subject_names)
                self.subject_names.append(enrolment.subject_name)
        before_grow = self.pipeline.gallery.grow_count
        labels_arr = np.full(len(emb), label, np.int32)
        try:
            if self.state is not None:
                # Write-ahead: the WAL record (fsynced per policy) lands
                # BEFORE the gallery mutation, both under the lifecycle's
                # enroll lock — a crash anywhere after the append replays
                # this enrolment on restart, and the 'enrolled' ack below
                # is a durability promise. A failed append raises: the
                # enrolment is rolled back, never acknowledged-but-lost.
                self.state.append_enrollment(
                    emb, labels_arr, subject=enrolment.subject_name,
                    label=label,
                    apply_fn=lambda: self.pipeline.gallery.add(emb, labels_arr),
                    embedder_version=enrol_version)
            else:
                self.pipeline.gallery.add(emb, labels_arr)  # ocvf-lint: boundary=wal-before-mutate -- explicit no-state-dir mode: nothing durable exists to sequence against, and the operator chose volatility
            grown = self.pipeline.gallery.grow_count - before_grow
            if grown:
                # Auto-grow saved the enrolment but forced a recompile-sized
                # stall on the next match — surface it so operators pre-size.
                self.metrics.incr(mn.GALLERY_GROWN, grown)
        except Exception as exc:
            # Roll back a name we just reserved: the gallery has no rows
            # for it, so leaving it would skew label->name indices.
            with self._enrol_lock:
                if (label == len(self.subject_names) - 1
                        and self.subject_names[label] == enrolment.subject_name):
                    self.subject_names.pop()
            if isinstance(exc, (DurabilityDegradedError, OSError)):
                # Storage-shaped refusal (ISSUE 15): the enrollment was
                # refused closed — never acknowledged, nothing durable
                # burned. Surface the explicit status (counting already
                # happened at the layer that refused: the lifecycle's
                # enrollments_refused_degraded / the WAL's
                # wal_append_errors) instead of killing the enrolment
                # thread with a silent traceback.
                logging.getLogger(__name__).warning(
                    "enrollment %r refused closed: %r",
                    enrolment.subject_name, exc)
                self._publish_status({
                    "status": "enroll_failed",
                    "subject": enrolment.subject_name,
                    "reason": ("durability_degraded"
                               if isinstance(exc, DurabilityDegradedError)
                               else "wal_error"),
                    "error": repr(exc)})
                return
            raise
        self.metrics.incr(mn.SUBJECTS_ENROLLED)
        self.connector.publish(
            STATUS_TOPIC,
            {
                "status": "enrolled",
                "subject": enrolment.subject_name,
                "label": label,
                "gallery_size": self.pipeline.gallery.size,
            },
        )
        self._run_commit_hooks()

    # ---- reload without drop (SURVEY.md §5.3) ----

    def reload_gallery(self, new_gallery) -> None:
        """Swap in a rebuilt gallery between batches (double-buffered)."""
        self.pipeline.gallery.swap_from(new_gallery)
        if self.tracker is not None:
            # Cached identities were verified against the OLD gallery's
            # labels/names: cold-start the cache (the embedder-version
            # fence catches cutovers, but a same-version swap can still
            # renumber labels).
            self.tracker.flush_all()
        self.connector.publish(STATUS_TOPIC, {"status": "reloaded",
                                              "gallery_size": self.pipeline.gallery.size})
        self._run_commit_hooks()
        if self.state is not None:
            # A swap is not WAL-representable (the log speaks in appended
            # rows): force a durable checkpoint of the NEW gallery. Until
            # it lands, a crash recovers the previous gallery plus every
            # acknowledged enrolment — the documented reload window.
            self.state.maybe_checkpoint(force=True)

    def _run_commit_hooks(self) -> None:
        """Notify commit watchers (see ``commit_hooks``); a raising hook
        must not kill the enrolment worker or the reload caller."""
        for hook in list(self.commit_hooks):
            try:
                hook()
            except Exception:  # noqa: BLE001 — watcher bugs stay theirs
                logging.getLogger(__name__).exception("commit hook failed")

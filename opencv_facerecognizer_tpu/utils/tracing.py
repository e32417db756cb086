"""Frame-lifecycle tracing: causal spans + flight recorder (observability
layer).

Before this module, a frame that vanished left behind aggregate counters
(``utils/metrics.py``) and nothing else — nobody could answer "what
happened to frame 48123" or "what was in flight when the soak wedged".
This layer records one causal **span** per stage a frame passes through:

    receive (verdict) -> intake -> queue_wait (batch ancestry) -> [batch
    trace: pop_wait, dispatch{leaves}, inflight_wait, ready_wait,
    publish{track_update}] -> settle (terminal outcome)

Every span carries ``parent``: the ``span`` id of the span that caused it
(0 for a root), so a reader can rebuild the tree and take a span's self
time as its duration minus its children's. Spans opened through
``Tracer.span`` are also ``jax.profiler.TraceAnnotation`` objects named
``ocvf:<stage>``: inside a profiling session they are events of the same
``.xplane.pb`` as the device's operations, on the thread that did the
work and on the profiler's own clock; outside one, opening them is a
flag test.

plus **lifecycle spans** for the slow machinery (checkpoints, WAL appends,
IVF retrains, brownout transitions, recovery). Spans are plain dicts held
in **per-topic bounded ring buffers** — a flight recorder, not an archive:

- **Emission is lock-free.** ``collections.deque`` appends are documented
  thread-safe in CPython, so the hot path (connector thread, serving loop,
  readback worker) never takes a lock to record a span; the tracer's
  ``_lock`` guards only ring *creation* and dump bookkeeping, and never
  nests inside (or around) any serving-path lock.
- **Sampling is deterministic.** The per-trace keep/drop verdict is a pure
  function of ``(seed, frame arrival index)`` (a Knuth multiplicative hash
  over frame-trace ids, which have their own counter — span emission and
  batch/lifecycle traces can never shift them), so a replayed chaos run
  with the logged seed samples exactly the same frames whenever the frame
  arrival order itself replays. ``sample=1.0`` traces everything — the
  mode the chaos accounting check runs in; lifecycle and batch spans are
  never sampled out.
- **Terminal accounting.** Every admitted frame must end in exactly one
  ``settle`` span whose ``outcome`` is either ``"completed"`` or the
  ledger drop-counter name it was counted under — the span-level mirror of
  the admission-ledger invariant ``admitted == completed + Σ drops``.
  ``account_spans`` reduces a span list back to that ledger shape so the
  chaos soak can cross-check them exactly.
- **Flight recorder.** ``dump()`` writes the rings atomically
  (``atomic_write_json`` — a crash mid-dump never leaves a torn file) to
  ``dump_dir/flight-<seq>-<reason>.json`` with bounded retention, on wedge
  detection, supervisor restart, SIGTERM drain, and dead-letter. Span
  timestamps are ``time.monotonic()``; each dump header carries paired
  monotonic + wall clocks so offline readers can convert.
- **JSONL export.** An optional ``span_sink`` (a ``RotatingJournal`` from
  ``make_span_journal``, sharing the dead-letter journal's bounded
  rotating machinery) streams every emitted span as one JSON line — for
  offline analysis beyond the ring's horizon. Off by default: it adds a
  file write per span, which is what the sampling knob is for.

Overhead: one dict + one deque append per span (plus, for ``span()``, a
small handle and the annotation object), ~4 spans per frame and ~15 per
batch at ``sample=1.0``. The bench gate (``bench_serving.py --smoke`` section
``tracing_overhead``) holds the fully-enabled e2e p50 regression under 3%.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.serialization import atomic_write_json

#: ring topic for batch-level spans (dispatch / ready_wait / publish /
#: dead_letter); frame spans ride the topic the frame arrived on.
BATCH_TOPIC = "_batch"
#: ring topic for lifecycle spans (checkpoint / wal_append / ivf_retrain /
#: brownout / recover ...).
LIFECYCLE_TOPIC = "_lifecycle"
#: the terminal span stage every admitted frame must reach exactly once.
SETTLE_STAGE = "settle"
#: ``settle`` outcome of a frame that published a result; every other
#: outcome is the admission-ledger drop-counter name it was counted under.
OUTCOME_COMPLETED = "completed"
#: ``settle`` outcome of a frame the stage-1 cascade rejected as
#: face-free: published with an empty face list, never dispatched to the
#: full detector — the ledger's ``completed_empty`` terminal status, a
#: sibling of completed, not a drop.
OUTCOME_COMPLETED_EMPTY = "completed_empty"
#: ``settle`` outcome of a frame answered FROM the temporal identity
#: cache (ISSUE 17): published with the cached identities, never
#: dispatched — the ledger's ``completed_cached`` terminal status, a
#: sibling of completed/completed_empty, not a drop.
OUTCOME_COMPLETED_CACHED = "completed_cached"

_HASH_MULT = 2654435761  # Knuth multiplicative hash (mod 2^32)

#: prefix of the profiler annotations ``Tracer.span`` opens
ANNOTATION_PREFIX = "ocvf:"
#: ``jax.profiler.TraceAnnotation`` once first needed, False where JAX
#: cannot be imported (then nothing is opened); tests patch it.
_annotation_factory: Any = None


def annotation(stage: str):
    """A profiler annotation ``ocvf:<stage>`` (a context manager), or the
    shared ``NULL_SPAN`` without JAX. Resolved on first use: ``utils``
    imports no JAX."""
    global _annotation_factory
    factory = _annotation_factory
    if factory is None:
        try:
            from jax.profiler import TraceAnnotation as factory
        except ImportError:
            factory = False
        _annotation_factory = factory
    return factory(ANNOTATION_PREFIX + stage) if factory else NULL_SPAN


class _NullSpan:
    """What ``Tracer.span`` gives for trace id 0, and what a site whose
    tracer is None enters in its place: shared, records nothing."""

    __slots__ = ()
    id = 0

    @property
    def attrs(self) -> Dict[str, Any]:
        return {}  # a fresh dict each time: writes to it vanish

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """An open span (``Tracer.span``): ``id`` exists from entry, so
    children can name it as their ``parent`` before it is emitted;
    ``attrs`` may be enriched until exit."""

    __slots__ = ("id", "attrs", "_tracer", "_trace", "_stage", "_topic",
                 "_parent", "_t0", "_note")

    def __init__(self, tracer: "Tracer", trace_id: int, stage: str,
                 topic: Optional[str], parent: int, attrs: Dict[str, Any]):
        self.attrs = attrs
        self._tracer = tracer
        self._trace = trace_id
        self._stage = stage
        self._topic = topic
        self._parent = parent

    def __enter__(self) -> "_Span":
        self.id = self._tracer.new_span_id()
        self._note = annotation(self._stage)
        self._t0 = time.monotonic()
        self._note.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.monotonic() - self._t0
        self._note.__exit__(*exc)
        self._tracer.emit(self._trace, self._stage, topic=self._topic,
                          t0=self._t0, dur=dur, parent=self._parent,
                          span_id=self.id, **self.attrs)
        return False


class Tracer:
    """Per-topic span ring buffers with deterministic sampling and an
    atomic flight-recorder dump (module docstring)."""

    def __init__(self, ring_size: int = 4096, sample: float = 1.0,
                 seed: int = 0, dump_dir: Optional[str] = None,
                 keep_dumps: int = 8, min_dump_interval_s: float = 1.0,
                 span_sink=None, metrics=None, fault_injector=None):
        self.ring_size = max(1, int(ring_size))
        self.sample = min(1.0, max(0.0, float(sample)))
        self.seed = int(seed)
        self.dump_dir = None if dump_dir is None else str(dump_dir)
        self.keep_dumps = max(1, int(keep_dumps))
        self.min_dump_interval_s = float(min_dump_interval_s)
        #: optional RotatingJournal-shaped sink (``append_line``) streaming
        #: every span as JSONL; non-strict — a sink failure never raises
        #: into the serving path (the journal counts its own errors).
        self.span_sink = span_sink
        #: optional shared Metrics surface for DUMP accounting only — span
        #: emission deliberately never touches the Metrics lock.
        self.metrics = metrics
        #: chaos hook (runtime.faults): the ``storage`` boundary fires
        #: inside ``dump`` before the atomic write, so an injected
        #: ENOSPC/EIO exercises the exact counted never-raise path a full
        #: disk does. None in production.
        self.fault_injector = fault_injector
        #: degraded-durability shed hook: while truthy, dumps are dropped
        #: before touching the disk (counted ``trace_dumps_shed``) — the
        #: flight recorder must never contend with the WAL for a dying
        #: disk's last bytes. Wired by DurabilityMonitor.attach_sinks.
        self.shed_fn = None
        # THREE id streams (next() on each is atomic in CPython):
        # - frame-trace ids (ODD): drawn in frame-arrival order ONLY, so
        #   the sampling verdict for "the Nth arriving frame" is a pure
        #   function of (seed, N) — batch/lifecycle traces and span
        #   emission (whose interleaving is thread-timing dependent) must
        #   not shift it between replayed runs;
        # - batch/lifecycle trace ids (EVEN): disjoint from frame ids so
        #   the two families can never collide in one span stream;
        # - span ids: one global sequence, drawn when a span is emitted
        #   or, for ``span()`` and pre-drawn ids, when it opens (a parent's
        #   id is below its children's); ``parent`` refers to them.
        self._frame_ids = itertools.count(0)
        self._aux_ids = itertools.count(1)
        self._span_ids = itertools.count(1)
        self._rings: Dict[str, deque] = {}
        # Guards ring creation + dump bookkeeping ONLY; never held across
        # emission, file I/O, or any call out of this class.
        self._lock = threading.Lock()
        self._dump_seq = itertools.count(1)
        self._last_dump_t: Dict[str, float] = {}
        if self.dump_dir is not None:
            os.makedirs(self.dump_dir, exist_ok=True)

    # ---- trace ids + sampling ----

    def start_trace(self, topic: str) -> int:
        """New frame trace id (odd), or 0 when sampled out (every ``emit``
        with trace id 0 is a no-op — the whole frame records nothing).
        Deterministic: the verdict is a pure function of (seed, arrival
        index) — frame ids come from their own counter, so concurrent
        span emission or batch/lifecycle traces can never shift which
        frames a replayed run samples (replay determinism then only needs
        the frame ARRIVAL order itself to be deterministic)."""
        tid = 2 * next(self._frame_ids) + 1
        if self.sample >= 1.0:
            return tid
        if self.sample <= 0.0:
            return 0
        h = ((tid + self.seed) * _HASH_MULT) & 0xFFFFFFFF
        return tid if h < self.sample * 4294967296.0 else 0

    def new_trace(self) -> int:
        """Unconditional trace id (even) for batch/lifecycle traces —
        never sampled out (they are few and carry the causal ancestry),
        and disjoint from the frame-trace id space."""
        return 2 * next(self._aux_ids)

    # ---- emission (the hot path: no locks) ----

    def _ring_for(self, topic: str) -> deque:
        ring = self._rings.get(topic)
        if ring is None:
            with self._lock:  # first span on a topic only
                ring = self._rings.setdefault(
                    topic, deque(maxlen=self.ring_size))
        return ring

    def new_span_id(self) -> int:
        """A span id drawn ahead of emission, for ``emit(span_id=)``: the
        sites that cannot be a ``with self.span(...)`` block but whose
        children must name them as ``parent``."""
        return next(self._span_ids)

    def emit(self, trace_id: int, stage: str, topic: Optional[str] = None,
             t0: Optional[float] = None, dur: float = 0.0,
             parent: int = 0, span_id: int = 0, **attrs: Any) -> None:
        """Record one finished span. ``t0`` is ``time.monotonic()`` at
        span start (defaults to now - dur); ``dur`` seconds on the same
        clock. ``parent`` is the ``span`` id of the span that caused this
        one (0: a root); ``span_id`` an id drawn by ``new_span_id`` when
        the span opened. No-op for trace id 0 (sampled out). Lock-free:
        one dict + one thread-safe deque append."""
        if not trace_id:
            return
        span: Dict[str, Any] = {
            "trace": trace_id,
            "span": span_id or next(self._span_ids),
            "parent": parent,
            "stage": stage,
            "t0": (time.monotonic() - dur) if t0 is None else t0,
            "dur": dur,
        }
        if attrs:
            span.update(attrs)
        self._ring_for(topic or BATCH_TOPIC).append(span)
        sink = self.span_sink
        if sink is not None:
            sink.append_line(json.dumps({"topic": topic or BATCH_TOPIC,
                                         **span}, default=repr))

    def span(self, trace_id: int, stage: str, topic: Optional[str] = None,
             parent: int = 0, **attrs: Any):
        """Context manager around one stage's work: on entry draws the
        span id, stamps ``t0`` and opens the profiler annotation
        ``ocvf:<stage>``; yields a handle (``.id`` for the children's
        ``parent=``, ``.attrs`` to enrich); on exit closes the annotation
        and emits with the measured duration, also when the body raised.
        Trace id 0 (sampled out): the shared ``NULL_SPAN``, nothing else.
        Like ``emit``, never leave the block while a serving-path lock is
        held: with a ``span_sink`` the emission writes a file."""
        if not trace_id:
            return NULL_SPAN
        return _Span(self, trace_id, stage, topic, parent, attrs)

    @contextlib.contextmanager
    def lifecycle(self, stage: str, **attrs: Any):
        """Span a lifecycle operation (``span`` on a trace of its own,
        lifecycle topic): yields a mutable attr dict the body may enrich;
        the span is emitted on exit with the measured duration, ``ok``
        False plus the error repr when the body raised (re-raised).

        Use this when the spanned body holds NO locks at exit. The
        runtime's own lifecycle sites (WAL append, checkpoint, IVF
        retrain) deliberately hand-roll the same t0/outcome/finally
        pattern instead: their emission must fire strictly AFTER their
        guard locks release — with a ``span_sink`` wired, ``emit`` does
        file I/O, and I/O under ``_enroll_lock``/``_ckpt_lock``/
        ``_train_lock`` is exactly what the blocking-under-lock
        discipline forbids."""
        with self.span(self.new_trace(), stage, topic=LIFECYCLE_TOPIC,
                       **attrs) as span:
            try:
                yield span.attrs
            except BaseException as exc:
                span.attrs.setdefault("ok", False)
                span.attrs.setdefault("error", repr(exc))
                raise
            finally:
                span.attrs.setdefault("ok", True)

    # ---- reading ----

    def topics(self) -> List[str]:
        with self._lock:
            return sorted(self._rings)

    def snapshot(self, topic: Optional[str] = None,
                 limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Spans currently held (oldest first), one topic or all merged in
        emission order. Emission is lock-free, so a concurrent append can
        interrupt iteration (CPython raises RuntimeError) — retry a few
        times rather than serialize the hot path against readers."""
        if topic is not None:
            rings = [self._rings.get(topic)]
        else:
            with self._lock:
                rings = list(self._rings.values())
        out: List[Dict[str, Any]] = []
        for ring in rings:
            if ring is None:
                continue
            for _ in range(8):
                try:
                    # Copy into a TEMP list first: a RuntimeError mid-extend
                    # would otherwise leave a partial copy in ``out`` and
                    # the retry would append the whole ring again —
                    # duplicated spans that break dump accounting.
                    copied = list(ring)
                except RuntimeError:
                    continue  # ring mutated mid-iteration: retry
                out.extend(copied)
                break
        if topic is None:
            out.sort(key=lambda s: s["span"])
        if limit is not None and len(out) > limit:
            out = out[-limit:]
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            per_topic = {t: len(r) for t, r in self._rings.items()}
        return {"ring_size": self.ring_size, "sample": self.sample,
                "spans_held": per_topic}

    # ---- the flight recorder ----

    def dump(self, reason: str, extra: Optional[Dict[str, Any]] = None,
             force: bool = False) -> Optional[str]:
        """Write the current rings atomically to ``dump_dir`` as
        ``flight-<seq>-<reason>.json``; returns the path, or None when no
        dump dir is configured or the per-reason rate limit suppressed it
        (``force`` bypasses the limit — the end-of-run / SIGTERM dumps
        must always land). Retention keeps the newest ``keep_dumps``
        files. Never raises: a recorder failure is counted
        (``trace_dump_errors``) — observability must not hurt serving.
        While the ``shed_fn`` hook reports degraded durability the dump
        is SHED before any I/O (``trace_dumps_shed``, exact accounting;
        ``force`` does not override — a forced dump against a disk known
        broken is still a doomed write competing with the WAL)."""
        if self.dump_dir is None:
            return None
        if self.shed_fn is not None and self.shed_fn():
            if self.metrics is not None:
                self.metrics.incr(mn.TRACE_DUMPS_SHED)
            return None
        now = time.monotonic()
        with self._lock:
            if (not force and self.min_dump_interval_s > 0
                    and now - self._last_dump_t.get(reason, float("-inf"))
                    < self.min_dump_interval_s):
                return None
            self._last_dump_t[reason] = now
            seq = next(self._dump_seq)
        record = {
            "schema": 1,
            "reason": str(reason),
            "seq": seq,
            "ts_unix": time.time(),
            "ts_monotonic": now,
            "sample": self.sample,
            "spans": {t: self.snapshot(t) for t in self.topics()},
        }
        if extra:
            record["extra"] = extra
        path = os.path.join(self.dump_dir, f"flight-{seq:06d}-{reason}.json")
        try:
            if self.fault_injector is not None:
                self.fault_injector.on_storage("trace_dump")
            atomic_write_json(path, record)
        except (OSError, TypeError, ValueError):
            if self.metrics is not None:
                self.metrics.incr(mn.TRACE_DUMP_ERRORS)
            return None
        if self.metrics is not None:
            self.metrics.incr(mn.TRACE_DUMPS)
        self._prune_dumps()
        return path

    def _prune_dumps(self) -> None:
        try:
            names = sorted(n for n in os.listdir(self.dump_dir)
                           if n.startswith("flight-") and n.endswith(".json"))
        except OSError:
            return
        for name in names[:-self.keep_dumps or None]:
            try:
                os.remove(os.path.join(self.dump_dir, name))
            except OSError:
                pass


# ---- helpers ----


def make_span_journal(path: str, max_bytes: int = 16 << 20,
                      backups: int = 2, metrics=None, fault_injector=None):
    """A bounded rotating JSONL sink for ``Tracer(span_sink=...)`` — the
    dead-letter journal's ``RotatingJournal`` base reused for span export
    (non-strict appends: a full disk costs spans, never serving; write
    failures and degraded-mode sheds land on the sink's OWN counters,
    ``trace_span_errors``/``trace_spans_shed``, so triage never confuses
    a dying span sink with a dying dead-letter journal).
    Imported lazily so utils keeps no module-level dependency on the
    runtime package."""
    from opencv_facerecognizer_tpu.runtime.journal import RotatingJournal

    return RotatingJournal(path, max_bytes=max_bytes, backups=backups,
                           metrics=metrics, fsync="never",
                           fault_injector=fault_injector,
                           error_counter=mn.TRACE_SPAN_ERRORS,
                           shed_counter=mn.TRACE_SPANS_SHED)


def account_spans(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Reduce frame spans to admission-ledger shape: ``completed`` count +
    per-outcome ``drops`` from the terminal ``settle`` spans, plus
    ``traced`` (distinct traces that emitted a ``receive`` span with an
    admitted verdict). With ``sample=1.0`` these must equal the service's
    ``ledger()`` exactly — the chaos soak's span-accounting check."""
    completed = 0
    completed_empty = 0
    completed_cached = 0
    drops: Dict[str, int] = {}
    admitted_traces = set()
    for span in spans:
        stage = span.get("stage")
        if stage == "receive" and span.get("verdict") == "admitted":
            admitted_traces.add(span.get("trace"))
        elif stage == SETTLE_STAGE:
            outcome = span.get("outcome")
            if outcome == OUTCOME_COMPLETED:
                completed += 1
            elif outcome == OUTCOME_COMPLETED_EMPTY:
                # Cascade early exits are terminal completions, not drops
                # — mirrored as their own ledger bucket.
                completed_empty += 1
            elif outcome == OUTCOME_COMPLETED_CACHED:
                # Track-cache exits (ISSUE 17): same terminal-completion
                # treatment, own bucket.
                completed_cached += 1
            elif outcome:
                drops[outcome] = drops.get(outcome, 0) + 1
    return {"traced": len(admitted_traces), "completed": completed,
            "completed_empty": completed_empty,
            "completed_cached": completed_cached, "drops": drops}

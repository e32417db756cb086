"""The ONE known-wiring map of the serving stack, shared by every checker.

PR 5's lock-order rule carried its own ``ATTR_HINTS`` table; the v2 rules
(host-sync, jit-recompile-hazard, wal-before-mutate, epoch-pairing) all need
the same "what class does ``self.<attr>`` dispatch to" knowledge plus a few
scope sets of their own.  Keeping them per-checker would mean four slowly
diverging copies of the runtime's wiring — this module is the single source
of truth; checkers import, never redefine.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, Tuple

#: Known wiring of ``self.<attr>`` (or any ``x.<attr>``) to the class whose
#: methods it dispatches to — the cross-module edges of the serving stack.
#: Used by lock-order call resolution AND the dataflow layer's
#: interprocedural call resolution.
ATTR_HINTS: Dict[str, str] = {
    "metrics": "Metrics",
    "tracer": "Tracer",
    "batcher": "FrameBatcher",
    "gallery": "ShardedGallery",
    "quantizer": "CoarseQuantizer",
    "journal": "DeadLetterJournal",
    "drop_log": "DeadLetterJournal",
    "wal": "EnrollmentWAL",
    "state": "StateLifecycle",
    "state_store": "StateLifecycle",
    "checkpoints": "CheckpointStore",
    "admission": "AdmissionController",
    "slo": "SLOMonitor",
    "connector": "JSONLConnector",
    "pipeline": "RecognitionPipeline",
    "replica": "ReadReplica",
    "router": "TopicRouter",
    "tailer": "WALTailer",
    "lease": "WriterLease",
    "rollout": "RolloutCoordinator",
    "stage": "ReEmbedStage",
    "parity": "DualScoreParity",
    # Ingest subsystem (PR 12): the service's ``self.ingest`` owns the
    # staging ring + decode pool; ``staging``/``staging_ring`` reach the
    # ring directly (the batcher holds it as ``_ring``), ``decoder`` is
    # the off-thread decode worker pool.
    "ingest": "IngestPipeline",
    "staging": "StagingRing",
    "staging_ring": "StagingRing",
    "decoder": "DecodeWorkerPool",
    # Cascade early-exit detection (ISSUE 13): the pipeline's
    # ``self.cascade`` is the stage-1 face-proposal model.
    "cascade": "FaceGate",
    # Degraded durability (ISSUE 15): the lifecycle's ``self.durability``
    # is the state machine whose probe thread owns the recovery tmp-file
    # write + fsync; ``span_sink`` is the tracer's JSONL journal (the
    # RotatingJournal base, with its own per-sink counters).
    "durability": "DurabilityMonitor",
    "span_sink": "RotatingJournal",
    # Partition tolerance (PR 16): link supervision and hedged dispatch
    # both live ON the router itself (per-replica state rides the
    # handles), so ``link``/``hedge`` attribute reads dispatch to
    # ``TopicRouter``; ``_faults`` is the shared injector whose transport
    # boundary the connector and router crossings call into (private
    # name on purpose — that is how every holder stores it).
    "link": "TopicRouter",
    "hedge": "TopicRouter",
    "_faults": "FaultInjector",
    # Temporal identity cache (ISSUE 17): the service's ``self.tracker``
    # is the per-replica track -> identity cache consulted on the
    # dispatch thread and updated on the readback worker.
    "tracker": "IdentityTracker",
    # Versioned model registry (ISSUE 18): ``self.registry`` is the durable
    # per-role version manifest every holder (lifecycle, service, replica)
    # consults; ``registry_swap`` is the live detector/cascade swap
    # coordinator whose parity window the readback worker feeds.
    "registry": "ModelRegistry",
    "registry_swap": "RegistrySwapCoordinator",
    # Protocol rules (v3): the batcher holds the staging ring as
    # ``self._ring``; it stores its tracer privately as ``self._tracer``.
    "_ring": "StagingRing",
    "_tracer": "Tracer",
}

#: The serving hot path: the overlapped loop (PR 2) lives in these modules.
#: host-sync scans exactly these; a stray blocking readback anywhere else is
#: either offline tooling or already under blocking-under-lock.
HOT_PATH_SUFFIXES: Tuple[str, ...] = (
    "runtime/recognizer.py",
    "runtime/batcher.py",
    "runtime/ingest.py",
    "parallel/pipeline.py",
    # The stage-1 cascade's forward runs per serving batch (ISSUE 13):
    # a stray blocking sync in the model module would land on the
    # dispatch path, so it is scanned like the rest of the hot loop.
    "models/cascade.py",
    # The temporal identity cache (ISSUE 17) runs per serving batch on
    # the dispatch AND readback threads: pure host NumPy by contract —
    # any device sync sneaking in here would stall the serving loop.
    "runtime/tracker.py",
    # The model registry's live-parity window (ISSUE 18) is fed from the
    # readback worker (``offer_live`` per published batch): its scoring is
    # host-side box math by contract, so the module is scanned like the
    # rest of the hot loop.
    "runtime/registry.py",
)

#: Modules that OWN the epoch-pairing protocol (PR 6): only they may touch
#: the guarded fields directly; everyone else goes through
#: ``gallery.data`` + ``gallery._ivf_data(data)``.
EPOCH_OWNER_SUFFIXES: Tuple[str, ...] = (
    "parallel/gallery.py",
    "parallel/quantizer.py",
)

#: Attributes reserved for the epoch-checked snapshot protocol.  ``_epoch``
#: is the invalidation fence; ``_data`` is the atomically-published snapshot
#: slot (both the gallery's GalleryData and the quantizer's IVFDeviceData).
EPOCH_GUARDED_ATTRS: FrozenSet[str] = frozenset({"_epoch", "_data"})

#: Single-field gallery snapshot properties: each one is an independent
#: ``self._data`` read, so reading two of them non-atomically can pair
#: fields across a concurrent swap.  Outside the owner modules, more than
#: one of these per function is a pairing hazard.
GALLERY_FIELD_PROPS: FrozenSet[str] = frozenset({"embeddings", "labels", "valid"})

#: Receiver names that denote a ShardedGallery in the runtime's wiring
#: (``gallery.add(...)``, ``self.pipeline.gallery.add(...)``).
GALLERY_RECEIVERS: FrozenSet[str] = frozenset({"gallery"})

#: Receiver names that denote the enrollment WAL.  Direct writes to it
#: outside runtime/state_store.py bypass the lifecycle's sequencing lock.
WAL_RECEIVERS: FrozenSet[str] = frozenset({"wal"})

#: WAL methods that mutate durable state (reads — replay/verify — are fine).
WAL_WRITE_METHODS: FrozenSet[str] = frozenset({
    "append", "append_record", "truncate", "truncate_below", "rotate",
})

#: The durability layers whose gallery/WAL mutations ARE the sanctioned
#: path: state_store owns the _enroll_lock -> append_enrollment
#: sequencing, and replication's read replicas APPLY rows the writer
#: already WAL-sequenced and fsynced — write-ahead holds for every one of
#: their gallery.add calls by construction (the row was durable before
#: the replica could even see it), so flagging them would invert the
#: rule's own invariant.
WAL_EXEMPT_SUFFIXES: Tuple[str, ...] = (
    "runtime/state_store.py",
    "runtime/replication.py",
)

#: Calls whose result is a DEVICE value (taint seeds for host-sync):
#: terminal attribute names of producer calls in the serving runtime.
DEVICE_PRODUCER_ATTRS: FrozenSet[str] = frozenset({
    "recognize_batch", "recognize_batch_packed", "device_put",
    # Stage-1 cascade pass: its result is a device array whose ONE
    # sanctioned materialize is the serving gate's decision readback
    # (annotated boundary in runtime/recognizer.py).
    "cascade_scores", "score_batch",
})

#: Host-sync sinks that are flagged UNCONDITIONALLY in hot-path modules —
#: their only purpose is to synchronize with the device.
SYNC_ATTRS: FrozenSet[str] = frozenset({
    "block_until_ready", "device_get", "item",
})

#: Host-materialization calls that are findings only when their argument is
#: device-tainted (``np.asarray(host_frame)`` in the batcher is fine; the
#: same call on a dispatched batch IS the readback).
MATERIALIZE_NAME_FUNCS: FrozenSet[str] = frozenset({"float", "int", "bool"})
MATERIALIZE_NP_FUNCS: FrozenSet[str] = frozenset({
    "asarray", "array", "ascontiguousarray",
})

#: Attribute loads on a traced/device value that yield STATIC Python data
#: (shapes are compile-time constants under jit) — never taint through them.
STATIC_VALUE_ATTRS: FrozenSet[str] = frozenset({
    "shape", "ndim", "dtype", "size", "weak_type", "sharding",
})

#: Container mutators that store their argument into the receiver (taint
#: flows receiver <- argument).
CONTAINER_STORE_METHODS: FrozenSet[str] = frozenset({
    "append", "appendleft", "extend", "add", "insert", "put", "put_nowait",
})

#: Methods on a device value that return HOST data without blocking —
#: ``is_ready`` is the serving loop's designed non-blocking probe.
HOST_RESULT_ATTRS: FrozenSet[str] = frozenset({"is_ready"})

#: Builtins whose result is host data regardless of argument taint
#: (``range(count)``'s index must not taint every subscript it reaches).
HOST_BUILTIN_FUNCS: FrozenSet[str] = frozenset({
    "len", "range", "enumerate", "hasattr", "isinstance", "getattr", "id",
})


# --------------------------------------------------------------------------
# v3 protocol rules (exit-path settlement / resource pairing / fence order)
# --------------------------------------------------------------------------

#: Observability surfaces that may legitimately be None (``metrics=None``
#: stats-only mode, untraced runs).  The exit-path engine models them as
#: WIRED: ``if self.metrics:`` guards are taken, so a guarded terminal
#: ``incr`` still pairs with its unconditional settle span.  Path analysis
#: must see the fully-instrumented execution — the None configuration
#: executes a strict subset of it.
OPTIONAL_SURFACE_ATTRS: FrozenSet[str] = frozenset({
    "metrics", "tracer", "_tracer", "journal", "drop_log", "_drop_log",
    "slo", "span_sink", "durability",
})

#: Classes whose methods own the frame-settlement protocol: every terminal
#: ledger ``incr`` must ride with exactly one settle span of the same
#: status on every path (settle-once).
SETTLE_SCOPE_CLASSES: FrozenSet[str] = frozenset({
    "RecognizerService", "FrameBatcher",
})

#: Settlement sinks: method name -> (trace-basis arg index, status arg
#: index), counted from the call's own args (``self`` excluded).  The
#: recognizer settles runs of frames (``_trace_settle``); the batcher
#: settles one frame per drop (``_emit_settle``).
SETTLE_SINKS: Dict[str, Tuple[int, int]] = {
    "_trace_settle": (0, 1),
    "_emit_settle": (0, 1),
}

#: The one prefix family whose members are terminal ledger statuses
#: (``batcher_dropped_<reason>`` — both the counter and the settle outcome
#: are minted from it, so the pairing is checked symbolically).
LEDGER_PREFIX_CONSTANTS: FrozenSet[str] = frozenset({
    "BATCHER_DROPPED_PREFIX",
})

#: Acquire/release pairings the resource-pairing engine enforces.  Each
#: entry is pure data — a new paired resource is one more dict here:
#:
#: - kind "acquire-release": ``acquire_methods`` are (class, method) pairs
#:   resolved through ATTR_HINTS; the bound result must reach a call whose
#:   attr is in ``release_attrs`` (passed the buffer bare), be handed off
#:   bare into another call/container, or be returned, on EVERY path —
#:   including raising ones (the crash handler's forfeit is the point).
#: - kind "seq-burn": an assignment burning ``burn_attr`` must be followed
#:   on every path by a ``<release_receiver>.<release_attr_prefix>*`` call
#:   (the WAL record or its abort tombstone).
#: - kind "context": calls to the (class, method) pairs are contextmanagers
#:   and must be entered with ``with`` — a bare call leaks the span.
RESOURCE_PAIRINGS: Tuple[Dict[str, Any], ...] = (
    {
        "kind": "acquire-release",
        "name": "staging-buffer",
        "acquire_methods": (("StagingRing", "acquire"),),
        "release_attrs": ("recycle", "forfeit", "release"),
        "module_suffixes": ("runtime/batcher.py", "runtime/ingest.py",
                           "runtime/recognizer.py"),
        "what": "staging-ring buffer",
    },
    {
        "kind": "seq-burn",
        "name": "wal-seq",
        "burn_attr": "_wal_seq",
        "release_receiver": "wal",
        "release_attr_prefix": "append_",
        "module_suffixes": ("runtime/state_store.py",),
        "what": "burned WAL sequence number",
    },
    {
        "kind": "context",
        "name": "tracer-span",
        "context_methods": (("Tracer", "lifecycle"), ("Tracer", "span")),
        "module_suffixes": (),  # everywhere
        "what": "span contextmanager",
    },
)

#: Modules that own the durable-swap fence protocol.
FENCE_MODULE_SUFFIXES: Tuple[str, ...] = (
    "runtime/state_store.py",
    "runtime/registry.py",
    "runtime/rollout.py",
)

#: Cutover scopes: functions implementing WAL-fence -> install.  Inside
#: them no install call may precede the fence append on any path.
FENCE_CUTOVER_FUNCS: FrozenSet[str] = frozenset({
    "perform_cutover", "perform_registry_cutover", "cutover",
})

#: The WAL fence records.
FENCE_APPEND_ATTRS: FrozenSet[str] = frozenset({
    "append_cutover", "append_registry_cutover",
})

#: Install calls fenced by them: the manifest write, the in-memory gallery
#: snapshot install, and the caller-supplied install hook.
FENCE_INSTALL_ATTRS: FrozenSet[str] = frozenset({
    "install", "load_snapshot",
})
FENCE_INSTALL_FN_NAMES: FrozenSet[str] = frozenset({"install_fn"})

#: Durable-install writers: these functions MUST write through the
#: ``atomic_write_*`` helpers (tmp+fsync+rename) and never a bare
#: ``open(..., "w")`` — a torn manifest/checkpoint is an unrecoverable
#: fence.
FENCE_DURABLE_WRITERS: Tuple[Tuple[str, str], ...] = (
    ("ModelRegistry", "_save_locked"),
    ("CheckpointStore", "save"),
)
ATOMIC_WRITE_PREFIX = "atomic_write_"

#: ledger-registry-coherence sites: where the terminal-status table from
#: utils/metric_names.py must be mirrored exactly.  Files absent from a
#: subset lint are skipped (run_lint.sh --changed).
COHERENCE_TRACING_SUFFIX = "utils/tracing.py"
COHERENCE_RECOGNIZER_SUFFIX = "runtime/recognizer.py"
COHERENCE_PROMTEXT_SUFFIX = "runtime/promtext.py"
COHERENCE_CHAOS_SUFFIX = "chaos_soak.py"


def path_matches(path: str, suffixes: Tuple[str, ...]) -> bool:
    norm = path.replace("\\", "/")
    return any(norm.endswith(s) for s in suffixes)

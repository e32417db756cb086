"""Canonical registry of every metric name on the shared ``Metrics``
surface.

The chaos soaks, the admission ledger, the overload bench and the tests
all compare counters *by string name* across a dozen files; a single typo
silently breaks an accounting invariant with no error anywhere.  Every
``incr``/``observe``/``set_gauge`` (and read-side ``counter``/
``percentile``/``counters_with_prefix``) call must use a constant from
this module, or a literal whose value appears here — enforced statically
by ``python -m tools.ocvf_lint`` (rule ``metrics-registry``).

Constants ending in ``_PREFIX`` name families whose suffix is dynamic
(``frames_rejected_<reason>``); the prefix itself is what gets validated.

Adding a metric: add the constant here first, then use it at the call
site.  Never inline a new name string at a call site.
"""

# ---- serving loop: frame lifecycle counters -------------------------------
FRAMES_ADMITTED = "frames_admitted"
FRAMES_COMPLETED = "frames_completed"
FRAMES_PROCESSED = "frames_processed"
FRAMES_MALFORMED = "frames_malformed"
FRAMES_DROPPED = "frames_dropped"
FRAMES_DROPPED_BROWNOUT = "frames_dropped_brownout"
FRAMES_DROPPED_CRASHED = "frames_dropped_crashed"
FRAMES_FAILED = "frames_failed"
FRAMES_DEAD_LETTERED = "frames_dead_lettered"
FACES_FOUND = "faces_found"
SUBJECTS_ENROLLED = "subjects_enrolled"
GALLERY_GROWN = "gallery_grown"

# ---- serving loop: batch counters -----------------------------------------
BATCHES_DISPATCHED = "batches_dispatched"
BATCHES_BUCKETED = "batches_bucketed"
BATCHES_FAILED = "batches_failed"
BATCHES_DEAD_LETTERED = "batches_dead_lettered"
#: face slots sent through the embedder: rung frames x max_faces of every
#: dispatched step (the pipeline reports it with the dispatch), so that a
#: share of peak counts the work that ran, not what a rung implies.
EMBED_SLOTS = "embed_slots"
#: frames sent through the detector: the rung's frames of every dispatched
#: step, real or padding (the pipeline reports it with the dispatch).
DETECT_FRAMES = "detect_frames"
#: tokens sent through an embedder that has a token axis: ``embed_slots`` x
#: the tokens a crop becomes (144 for the ViT at 112x112, patch 9); stays 0
#: for the convolutional embedders.
EMBED_TOKENS = "embed_tokens"
#: face slots sent through an embedder whose attention lowered to the Pallas
#: kernel (``ops.vit_attention``): equals ``embed_slots`` where every step
#: did (the ViT on a TPU), stays 0 everywhere else.
EMBED_ATTN_KERNEL_SLOTS = "embed_attn_kernel_slots"
LOOP_CRASHES = "loop_crashes"
DISPATCH_FAILURES = "dispatch_failures"
DISPATCH_RETRIES = "dispatch_retries"
READBACK_ERRORS = "readback_errors"
CPU_FALLBACKS = "cpu_fallbacks"
DEGRADED_TRANSITIONS = "degraded_transitions"
DEGRADED_RECOVERIES = "degraded_recoveries"

# ---- serving loop: busy time, counted where the leaf spans are cut ---------
# Seconds summed (counters, not windows): always on, one ``incr`` per
# counter per batch, so a window's deltas tile the time of each thread.
#: the serving loop's wall time by leaf, ``loop_s_<stage>`` for each stage
#: of LOOP_LEAVES (the ``Tracer`` span of the same name covers the same
#: block), and ``loop_s_unnamed``: the iteration's wall time under no leaf.
LOOP_S_PREFIX = "loop_s_"
LOOP_LEAVES = ("pop_wait", "track_cache", "gate_enqueue", "gate_wait",
               "compact", "track_miss", "settle_early", "upload",
               "step_enqueue", "inflight_wait")
#: seconds of CPU the loop's thread ran (``time.thread_time()``), one
#: read an iteration where ``loop_s_*`` are flushed: the wall time outside
#: the waits the loop makes by design (``loop_s_*`` less ``gate_wait``,
#: ``inflight_wait`` and ``batcher_pop_wait_s``) less this is what the
#: thread waited for the interpreter or a lock. Native code that runs
#: with the interpreter released counts as CPU. One read a batch and not
#: two a leaf because the clock is a system call: 5.7 us on the chip's
#: host against 0.07 for ``time.monotonic()`` (PERF.md section 6, PR 41).
LOOP_CPU_S = "loop_cpu_s"
#: iterations of the serving loop that popped a batch (the denominator of
#: every ``loop_s_*`` per-batch quotient).
LOOP_BATCHES = "loop_batches"
#: batches whose stage-1 gate went onto the device's queue ahead of the
#: step of the batch before (a closed batch was already waiting when that
#: step was about to be enqueued); over ``batches_dispatched``, the share
#: of steps the chip had queued before it finished the one before.
BATCHES_GATED_AHEAD = "batches_gated_ahead"
#: early-exit frames (track-cache hits and gate rejections) published
#: after their batch's step was enqueued, not ahead of it; over
#: ``frames_completed_empty`` + ``frames_completed_cached``, the share of
#: early exits whose publish the chip did not wait on (the rest belong to
#: batches with no survivor, which enqueue no step).
EARLY_EXITS_DEFERRED = "early_exits_deferred"
#: the readback worker's ``_publish`` as a whole, beside its count
#: ``frames_completed``; of it, the ``tracker.update`` calls and their count.
PUBLISH_S = "publish_s"
PUBLISH_S_TRACK_UPDATE = "publish_s_track_update"
TRACK_UPDATES = "track_updates"
#: ``publish_s``'s CPU twin (the worker's ``time.thread_time()`` at the
#: same two instants), and the worker's CPU as a whole, from one batch's
#: second read to the next's: what it ran outside ``_publish`` too (the
#: materialize, the per-frame latency observations, the recycle).
PUBLISH_CPU_S = "publish_cpu_s"
READBACK_CPU_S = "readback_cpu_s"
#: the connector thread's handler from entry to the return of
#: ``batcher.put`` (on the JPEG path plus the decode worker's hand-over),
#: admitted frames only: beside ``frames_admitted``.
INTAKE_S = "intake_s"
#: seconds of CPU the thread that runs the handler ran, in the handler
#: and out of it (the connector's receive loop; a benchmark's generator):
#: its own CPU clock read once in ``INTAKE_CPU_EVERY`` admitted frames and
#: counted whole. An upper bound of the CPU inside ``intake_s``, so
#: ``intake_s`` less it is at least what the handler waited (for the
#: interpreter after the native decode, for the batcher's lock).
INTAKE_THREAD_CPU_S = "intake_thread_cpu_s"
#: admitted wire-form frames (``__frame__``: base64) that the native
#: decoder turned into their array, the interpreter's lock released; over
#: ``frames_admitted``, 100 % where every frame arrives in wire form and
#: clean, 0 where the library is missing (no compiler) and the standard
#: decoder serves. A frame the native decoder declines (line breaks, a
#: wrong size, corrupt text) goes to the standard one and is not counted.
FRAMES_DECODED_NATIVE = "frames_decoded_native"

# ---- serving loop: latency windows (observe) ------------------------------
WARMUP = "warmup"
QUEUE_WAIT = "queue_wait"
DISPATCH = "dispatch"
PUBLISH = "publish"
BATCH_LATENCY = "batch_latency"
READY_WAIT = "ready_wait"
#: per-frame end-to-end latency (batcher enqueue -> result publish), the
#: SLO layer's headline histogram family; the ``_interactive`` window is
#: the same observation restricted to interactive-priority frames.
E2E_LATENCY = "e2e_latency"
E2E_LATENCY_INTERACTIVE = "e2e_latency_interactive"

# ---- cascade early-exit detection (models.cascade + the serving gate) ------
#: terminal admission-ledger status for frames the stage-1 cascade scored
#: face-free: published with an empty face list, never dispatched to the
#: full detect->crop->embed->match step. NOT a drop — the ledger invariant
#: is ``admitted == completed + completed_empty + Σ drops``.
FRAMES_COMPLETED_EMPTY = "frames_completed_empty"
#: frames the stage-1 pass scored (rejected + passed), and whole batches
#: that exited at the cascade (zero survivors — no stage-2 dispatch).
CASCADE_FRAMES_SCORED = "cascade_frames_scored"
CASCADE_BATCH_EXITS = "cascade_batch_exits"
#: a stage-1 scoring pass raised: the batch fails OPEN to the full
#: detector (availability beats the early-exit win), counted loudly.
CASCADE_ERRORS = "cascade_errors"
#: host wall of one stage-1 pass incl. its tiny [B] readback (observe).
CASCADE_SCORE = "cascade_score"
#: first-class /prom gauges: cumulative reject/pass fractions of scored
#: frames, and the EFFECTIVE operating threshold (incl. the brownout
#: tightening notch) the last batch was gated at.
CASCADE_REJECT_RATE = "cascade_reject_rate"
CASCADE_PASS_RATE = "cascade_pass_rate"
CASCADE_THRESHOLD = "cascade_threshold"

# ---- temporal identity cache (runtime.tracker, ISSUE 17) -------------------
#: terminal admission-ledger status for frames served FROM the track
#: cache: published with the cached identities (``exit: track_cache``),
#: never dispatched — a sibling of ``completed``/``completed_empty``, not
#: a drop. The ledger invariant is ``admitted == completed +
#: completed_empty + completed_cached + Σ drops``.
FRAMES_COMPLETED_CACHED = "frames_completed_cached"
#: cache consults (one per tracked frame entering _serve_one) and the
#: frames they answered from the cache.
TRACK_LOOKUPS = "track_lookups"
TRACK_CACHE_HITS = "track_cache_hits"
#: /prom gauges: cumulative hit fraction of lookups, and live tracks.
TRACK_CACHE_HIT_RATE = "track_cache_hit_rate"
TRACKS_LIVE = "tracks_live"
TRACKS_CREATED = "tracks_created"
TRACKS_CONFIRMED = "tracks_confirmed"
#: full verifies forced by the schedule (every reverify_frames) or by
#: appearance drift under a live track.
TRACK_REVERIFIES = "track_reverifies"
#: per-reason flush family ``track_flushes_<identity|ambiguity|version|
#: lost|reset>`` (see runtime/tracker.py module docstring).
TRACK_FLUSHES_PREFIX = "track_flushes_"
#: whole batches that settled entirely from the cache (no dispatch), and
#: tracker call failures (fail OPEN: the frame takes the full path).
TRACK_BATCH_EXITS = "track_batch_exits"
TRACK_ERRORS = "track_errors"
#: seconds callers (the serving loop's ``lookup`` / ``note_misses``, the
#: readback worker's ``update``) spent acquiring the tracker's one lock,
#: and the acquisitions: the lock is held for registry bookkeeping only,
#: so the quotient is what one thread's bookkeeping costs the other.
TRACKER_LOCK_WAIT_S = "tracker_lock_wait_s"
TRACKER_LOCK_ACQUIRES = "tracker_lock_acquires"

# ---- admission / brownout (overload layer) --------------------------------
#: per-reason rejection family: ``frames_rejected_<reason>``
FRAMES_REJECTED_PREFIX = "frames_rejected_"
BROWNOUT_LEVEL = "brownout_level"
BROWNOUT_TRANSITIONS = "brownout_transitions"
BROWNOUT_RECOVERIES = "brownout_recoveries"

# ---- batcher ---------------------------------------------------------------
BATCHER_FRAMES_OFFERED = "batcher_frames_offered"
BATCHER_FRAMES_BATCHED = "batcher_frames_batched"
#: per-reason drop family: ``batcher_dropped_<reason>``
BATCHER_DROPPED_PREFIX = "batcher_dropped_"
BATCHER_DROPPED_MALFORMED = "batcher_dropped_malformed"
BATCHER_DROPPED_CLOSED = "batcher_dropped_closed"
BATCHER_DROPPED_OVERFLOW = "batcher_dropped_overflow"
BATCHER_DROPPED_STALE = "batcher_dropped_stale"
BATCHER_BATCHES_SIZE = "batcher_batches_size"
BATCHER_BATCHES_DEADLINE = "batcher_batches_deadline"
BATCHER_BUFFER_REUSE = "batcher_buffer_reuse"
BATCHER_FLUSH_DEADLINE_MS = "batcher_flush_deadline_ms"
#: seconds ``put`` (the intake threads) and ``get_batch`` (the serving
#: loop) spent acquiring the batcher's one lock, and the acquisitions:
#: one clock pair round each acquire, summed under the lock itself and
#: handed over by ``get_batch`` once a batch. The condition's wait for
#: frames is not in it (that is the loop's leaf ``pop_wait``).
BATCHER_LOCK_WAIT_S = "batcher_lock_wait_s"
BATCHER_LOCK_ACQUIRES = "batcher_lock_acquires"
#: seconds ``get_batch`` spent in its condition's waits (for frames, for
#: a batch to close, for a staging buffer): the part of the loop's leaf
#: ``pop_wait`` that is a wait by design; the rest of the leaf is the
#: batch's assembly. Handed over with the lock's sums.
BATCHER_POP_WAIT_S = "batcher_pop_wait_s"

# ---- ingest pipeline (runtime.ingest) ---------------------------------------
#: staging-ring buffer allocations: the per-rung preallocation at
#: construction plus outage heals (a forfeited buffer replaced after a
#: dead-letter). Steady-state serving must never move this counter — the
#: zero-alloc assertion the ingest tests pin.
INGEST_STAGING_ALLOCS = "ingest_staging_allocs"
INGEST_STAGING_REUSE = "ingest_staging_reuse"
#: an acquire found every fitting rung empty (ring exhausted): the batch
#: stays queued and admission backpressure (reason ``staging``) sheds new
#: intake — never an allocation.
INGEST_STAGING_EXHAUSTED = "ingest_staging_exhausted"
#: buffers the service told the ring it will never get back (dead-letter /
#: crash paths keep the staging array out of circulation because the
#: backend's async H2D read may still be pending).
INGEST_STAGING_FORFEITS = "ingest_staging_forfeits"
INGEST_STAGING_FREE = "ingest_staging_free"
#: host-side device-upload enqueue time (seconds, observe) and the bytes
#: shipped across H2D — bytes/frame in uint8 mode is the 4x story.
INGEST_UPLOAD = "ingest_upload"
INGEST_UPLOAD_BYTES = "ingest_upload_bytes"

# ---- compressed-frame intake: decode worker pool (runtime.ingest) -----------
DECODE_LATENCY = "decode_latency"
DECODE_QUEUE_DEPTH = "decode_queue_depth"
DECODE_FRAMES = "decode_frames"
DECODE_ERRORS = "decode_errors"
#: admission-ledger drop bucket: an ADMITTED compressed frame that never
#: became a pixel frame (corrupt/truncated payload, or decode backlog
#: overflow) — journaled with reason ``decode_error``/``decode_backlog``.
FRAMES_DROPPED_DECODE = "frames_dropped_decode"

# ---- connectors ------------------------------------------------------------
CONNECTOR_MALFORMED_LINES = "connector_malformed_lines"
CONNECTOR_PEER_DISCONNECTS = "connector_peer_disconnects"
CONNECTOR_RECONNECTS = "connector_reconnects"
CONNECTOR_RECONNECT_FAILURES = "connector_reconnect_failures"
CONNECTOR_STALLED_CLIENTS_DROPPED = "connector_stalled_clients_dropped"

# ---- transport fault boundary (runtime.faults, ISSUE 16) -------------------
#: per-kind family of transport faults a send/recv crossing actually
#: enacted: ``transport_fault_<partition|slow|drop|duplicate|reorder|
#: half_open>``.  Counted by the CALLER that crossed the boundary (router
#: forward/fan-in, socket connector send/recv), so the metrics surface and
#: the injector's own ``injected`` ledger can be cross-checked exactly.
TRANSPORT_FAULTS_PREFIX = "transport_fault_"

# ---- idempotent routing: frame-id dedup (ISSUE 16) -------------------------
#: duplicate deliveries of an already-admitted frame id, refused at
#: replica intake BEFORE admission — like ``frames_rejected_<reason>``
#: these sit OUTSIDE the admission ledger by design, so
#: ``admitted == completed + completed_empty + Σ drops`` holds exactly
#: under duplication, retries, and failover re-sends.
FRAMES_DEDUPED = "frames_deduped"
#: duplicate results for one frame id swallowed at the router's fan-in
#: (the second copy of a hedged or duplicated frame's result) — the
#: guarantee that a result is never double-published upstream.
ROUTER_RESULTS_DEDUPED = "router_results_deduped"

# ---- link supervision (runtime.replication.TopicRouter, ISSUE 16) ----------
#: application-level heartbeats: pings the router sent down each replica
#: link, and pongs that made it back through the transport boundary.
LINK_HEARTBEATS_SENT = "link_heartbeats_sent"
LINK_HEARTBEATS_RECEIVED = "link_heartbeats_received"
#: per-replica link gauge family ``link_state_<replica>``: 1 = pong seen
#: within the deadline, 0 = link down (partitioned / half-open — the
#: replica is excluded from rendezvous until the link heals).
LINK_STATE_PREFIX = "link_state_"
#: link up->down / down->up transitions, and the current count of down
#: links (gauge — the ``link_health`` SLO objective's numerator).
LINK_FAILURES = "link_failures"
LINK_RECOVERIES = "link_recoveries"
LINKS_DOWN = "links_down"

# ---- dead-letter journal ---------------------------------------------------
JOURNAL_ERRORS = "journal_errors"
JOURNAL_RECORDS = "journal_records"
JOURNAL_FRAMES = "journal_frames"
#: a pre-existing journal file whose last line had no terminating newline
#: (an ENOSPC/crash-torn append from a previous process): sealed at open
#: so the remnant stays one isolated unparseable line — never the prefix
#: of a new acknowledged record.
JOURNAL_TORN_TAILS = "journal_torn_tails"
#: records deliberately NOT written because durability is degraded (the
#: non-critical-sink shed posture): exact accounting, not a silent
#: best-effort swallow.
JOURNAL_SHED = "journal_shed"

# ---- durable state: checkpoints --------------------------------------------
CHECKPOINTS_WRITTEN = "checkpoints_written"
CHECKPOINTS_CORRUPT = "checkpoints_corrupt"
CHECKPOINTS_VERSION_SKIPPED = "checkpoints_version_skipped"
CHECKPOINT_READ_ERRORS = "checkpoint_read_errors"
CHECKPOINT_FAILURES = "checkpoint_failures"
CHECKPOINTS_SKIPPED_INFLIGHT = "checkpoints_skipped_inflight"
CHECKPOINTS_DEFERRED_PENDING = "checkpoints_deferred_pending"
#: retention-sweep removals (stale tmp files, pruned checkpoints,
#: quarantine excess) that failed with an OSError — previously a silent
#: ``pass``; a GC that stops GC-ing on a sick disk must be visible.
CHECKPOINT_GC_ERRORS = "checkpoint_gc_errors"

# ---- durable state: enrollment WAL -----------------------------------------
WAL_APPENDS = "wal_appends"
WAL_ROWS_APPENDED = "wal_rows_appended"
WAL_ABORTS = "wal_aborts"
WAL_CORRUPT_RECORDS = "wal_corrupt_records"
WAL_SKIPPED_RECORDS = "wal_skipped_records"
WAL_REPLAYED_RECORDS = "wal_replayed_records"
WAL_REPLAYED_ROWS = "wal_replayed_rows"
WAL_TAIL_REPLAYED_ROWS = "wal_tail_replayed_rows"
WAL_TORN_TAILS_SEALED = "wal_torn_tails_sealed"
WAL_OVER_BYTES = "wal_over_bytes"
WAL_ROWS = "wal_rows"
#: strict WAL appends that FAILED with an OSError (ENOSPC/EIO — the
#: enrollment was refused, never acknowledged): the signal the
#: degraded-durability state machine counts toward its flip.
WAL_APPEND_ERRORS = "wal_append_errors"
STATE_RECOVERIES = "state_recoveries"

# ---- degraded-durability state machine (runtime.resilience, ISSUE 15) ------
#: gauge: 0 = durability armed (WAL appends acknowledged durable),
#: 1 = durability_degraded (sustained storage failure — enrollments are
#: refused closed, serving/read traffic continues, non-critical sinks
#: shed). Exported on /prom; /health carries the disk objective.
DURABILITY_STATE = "durability_state"
DURABILITY_DEGRADED_TRANSITIONS = "durability_degraded_transitions"
#: degraded -> armed recoveries (the background probe's tmp write+fsync
#: succeeded and re-armed acknowledged durability).
DURABILITY_REARMS = "durability_rearms"
DURABILITY_PROBES = "durability_probes"
DURABILITY_PROBE_FAILURES = "durability_probe_failures"
#: enroll commands / finished enrolments refused CLOSED while degraded
#: (explicit ``durability_degraded`` status — the ack never lies).
ENROLLMENTS_REFUSED_DEGRADED = "enrollments_refused_degraded"
#: split-brain safety (ISSUE 16): the monitor's lease-directory
#: reachability check failed — a writer partitioned from its own lease
#: volume must flip durability-degraded rather than ack enrollments the
#: fleet can't see.
DURABILITY_LEASE_CHECK_FAILURES = "durability_lease_check_failures"

# ---- disk-pressure watermarks (runtime.resilience, ISSUE 15) ---------------
#: statvfs free bytes on the state volume (gauge, refreshed by the
#: durability monitor's tick) and the derived pressure state: 0 = ok,
#: 1 = warn (below the low watermark — preemptive WAL compaction +
#: retention shrink fired), 2 = critical (the degraded flip pre-empted
#: ENOSPC).
DISK_FREE_BYTES = "disk_free_bytes"
DISK_PRESSURE_STATE = "disk_pressure_state"
#: warn-watermark actions: forced checkpoint-compactions of the WAL, and
#: retention shrinks (checkpoint keep / flight-dump keep / journal
#: backups tightened to their floor).
DISK_PRESSURE_COMPACTIONS = "disk_pressure_compactions"
DISK_PRESSURE_RETENTION_SHRINKS = "disk_pressure_retention_shrinks"

# ---- IVF coarse quantizer (parallel.quantizer / ops.ivf_match) -------------
IVF_BUILDS = "ivf_builds"
IVF_BUILD_FAILURES = "ivf_build_failures"
IVF_RETRAINS_SKIPPED_INFLIGHT = "ivf_retrains_skipped_inflight"
IVF_INVALIDATIONS = "ivf_invalidations"
IVF_INCREMENTAL_ROWS = "ivf_incremental_rows"
IVF_SPILL_ROWS = "ivf_spill_rows"
IVF_SIDECAR_WRITES = "ivf_sidecar_writes"
IVF_SIDECAR_LOADS = "ivf_sidecar_loads"
IVF_SIDECAR_STALE = "ivf_sidecar_stale"
IVF_SIDECAR_ERRORS = "ivf_sidecar_errors"

# ---- sharded gallery installs (parallel.gallery) ---------------------------
#: tp shards the gallery's rows are split over (gauge; 1 on one chip).
GALLERY_SHARDS = "gallery_shards"
#: rows that crossed the host->device link into the gallery: an ``add``
#: of n rows counts n, a whole-set host install the rows it holds — never
#: a tier's capacity.
GALLERY_ROWS_UPLOADED = "gallery_rows_uploaded"
#: whole-set installs adopted from device arrays
#: (``ShardedGallery.install_device_rows``): nothing crossed the link.
GALLERY_BULK_INSTALLS = "gallery_bulk_installs"

# ---- tracing / flight recorder / exposition (utils.tracing, runtime.expo) --
TRACE_DUMPS = "trace_dumps"
TRACE_DUMP_ERRORS = "trace_dump_errors"
#: flight dumps deliberately not written while durability is degraded
#: (shed, exact accounting — the recorder must never contend with the
#: WAL for a dying disk's last bytes).
TRACE_DUMPS_SHED = "trace_dumps_shed"
#: span-JSONL sink write failures / degraded-mode sheds — per-sink
#: accounting, distinct from the dead-letter journal's ``journal_*``.
TRACE_SPAN_ERRORS = "trace_span_errors"
TRACE_SPANS_SHED = "trace_spans_shed"
EXPO_REQUESTS = "expo_requests"
EXPO_ERRORS = "expo_errors"

# ---- signals layer: SLO / health / watchdogs (runtime.slo) -----------------
#: health state machine gauge: 0 = ok, 1 = warn, 2 = critical.
HEALTH_STATE = "health_state"
SLO_EVALUATIONS = "slo_evaluations"
SLO_TRANSITIONS = "slo_transitions"
#: a gauge objective's ``value_fn`` raised — the probe is dead, its burn
#: reads 0 (no data is not a breach), but the failure is never silent.
SLO_PROBE_FAILURES = "slo_probe_failures"
#: a backstop ticker's ``SLOMonitor.tick()`` raised — the EVALUATION
#: failed, distinct from a dead gauge probe (``slo_probe_failures``):
#: alerting on this chases the monitor, not an objective's value_fn.
SLO_TICK_ERRORS = "slo_tick_errors"
#: per-objective burn-rate gauge family: ``slo_burn_<objective>`` (the
#: max of the short- and long-window burn rates at last evaluation).
SLO_BURN_PREFIX = "slo_burn_"
#: warn-level watchdog event counter family: ``slo_events_<reason>``
#: (e.g. ``slo_events_recompile_post_warmup``).
SLO_EVENTS_PREFIX = "slo_events_"
#: jit-cache misses observed on serving dispatches AFTER warmup compiled
#: the whole bucket ladder — each one is a mid-serving XLA compile the
#: prewarm design exists to prevent (the recompile watchdog's counter).
RECOMPILES_POST_WARMUP = "recompiles_post_warmup"

# ---- replication: writer lease / WAL-tailing read replicas -----------------
REPLICATION_LEASE_ACQUIRED = "replication_lease_acquired"
REPLICATION_LEASE_CONFLICTS = "replication_lease_conflicts"
REPLICATION_POLLS = "replication_polls"
REPLICATION_POLL_ERRORS = "replication_poll_errors"
REPLICATION_RECORDS_APPLIED = "replication_records_applied"
REPLICATION_ROWS_APPLIED = "replication_rows_applied"
REPLICATION_CORRUPT_RECORDS = "replication_corrupt_records"
REPLICATION_WAL_REOPENS = "replication_wal_reopens"
REPLICATION_RESYNCS = "replication_resyncs"
REPLICATION_ABORTS_AFTER_APPLY = "replication_aborts_after_apply"
REPLICATION_ENROLL_REJECTED = "replication_enroll_rejected"
#: replica staleness gauges: WAL rows visible but not yet applied, and the
#: age (seconds) of the oldest row at the moment the replica applied it.
REPLICATION_LAG_ROWS = "replication_lag_rows"
REPLICATION_LAG_S = "replication_lag_s"

# ---- embedder rollout (runtime.rollout + the version-fenced state) ---------
#: rollout phase gauge: 0 idle, 1 staging, 2 parity, 3 ready, 4 cutover,
#: 5 done (``runtime.rollout.PHASE_CODES``).
ROLLOUT_PHASE = "rollout_phase"
#: contiguous re-embedded rows durable in the stage file (the resume
#: watermark) vs the gallery rows the rollout must cover.
ROLLOUT_STAGED_ROWS = "rollout_staged_rows"
ROLLOUT_TOTAL_ROWS = "rollout_total_rows"
#: dual-score parity window: sliding top-1 agreement of old vs new
#: embedder on live traffic, and the sample count behind it.
ROLLOUT_PARITY_AGREEMENT = "rollout_parity_agreement"
ROLLOUT_PARITY_SAMPLES = "rollout_parity_samples"
ROLLOUT_STAGE_CHUNKS = "rollout_stage_chunks"
ROLLOUT_STAGE_RESUMES = "rollout_stage_resumes"
ROLLOUT_STAGE_ERRORS = "rollout_stage_errors"
ROLLOUT_CUTOVERS = "rollout_cutovers"
#: recovery found a fsynced cutover fence with no post-cutover checkpoint
#: and completed the swap from the staged shard set.
ROLLOUT_CUTOVERS_COMPLETED_RECOVERY = "rollout_cutovers_completed_recovery"
ROLLOUT_CUTOVER_BLOCKED = "rollout_cutover_blocked"
ROLLOUT_ROLLBACKS = "rollout_rollbacks"
#: the serving embedder version gauge (stamped into checkpoints, WAL rows
#: and published results; one served shard set holds exactly one).
ROLLOUT_EMBEDDER_VERSION = "rollout_embedder_version"
#: version-fence rejections: an enrollment whose embeddings carry another
#: version than the serving gallery (failed closed, no seq burned).
ROLLOUT_VERSION_MISMATCHES = "rollout_version_mismatches"
#: rows a replay/tail consumer REFUSED to apply across the version fence
#: (can only arise from damaged state — loud, never mixed in).
ROLLOUT_VERSION_SKIPPED_ROWS = "rollout_version_skipped_rows"
#: a read replica parked on a cutover fence, waiting for the new-version
#: checkpoint to re-anchor on (gauge 1/0), and the re-anchors completed.
ROLLOUT_REPLICA_AWAITING = "rollout_replica_awaiting"
ROLLOUT_REPLICA_REANCHORS = "rollout_replica_reanchors"
#: parity/live-traffic observation hook failures (publish path; counted,
#: never propagated into the serving loop).
ROLLOUT_OBSERVE_ERRORS = "rollout_observe_errors"
#: cutover WAL fence records appended.
WAL_CUTOVER_RECORDS = "wal_cutover_records"

# ---- versioned model registry (runtime.registry, ISSUE 18) -----------------
#: per-role served-version gauge family ``model_version_<role>`` (the
#: /prom mirror of the durable manifest: embedder, detector, cascade).
MODEL_VERSION_PREFIX = "model_version_"
#: registry swap phase gauge: 0 idle, 1 parity, 2 ready, 3 cutover,
#: 4 watch, 5 done, 6 rolled_back (``runtime.registry.PHASE_CODES``).
REGISTRY_PHASE = "registry_phase"
#: detection-parity window (old vs candidate detector, box-overlap
#: verdict match on live sampled frames) and the sample count behind it.
REGISTRY_PARITY_AGREEMENT = "registry_parity_agreement"
REGISTRY_PARITY_SAMPLES = "registry_parity_samples"
#: fenced registry swaps performed, and swaps the parity gate refused.
REGISTRY_SWAPS = "registry_swaps"
REGISTRY_SWAPS_BLOCKED = "registry_swaps_blocked"
#: recovery found a fsynced registry fence whose manifest install never
#: ran and COMPLETED it (staged params verified) / cleanly ABANDONED it
#: (params missing or damaged — the version number is retired).
REGISTRY_SWAPS_COMPLETED_RECOVERY = "registry_swaps_completed_recovery"
REGISTRY_SWAPS_ABANDONED_RECOVERY = "registry_swaps_abandoned_recovery"
#: post-cutover watch regressions rolled back automatically (each one
#: forces a ``registry_auto_rollback`` flight dump).
REGISTRY_AUTO_ROLLBACKS = "registry_auto_rollbacks"
#: FaceGate retrains riding a detector swap (``evaluate_gate`` scores
#: stage 1 against detector verdicts, so the pair cuts over together).
REGISTRY_GATE_RETRAINS = "registry_gate_retrains"
#: eager tracker/cascade cache flushes on a role's cutover.
REGISTRY_CACHE_FLUSHES = "registry_cache_flushes"
#: live-observation hook failures on the publish path (counted, never
#: propagated into the serving loop — like rollout_observe_errors).
REGISTRY_OBSERVE_ERRORS = "registry_observe_errors"
#: registry_cutover WAL fence records appended, and abandon tombstones.
WAL_REGISTRY_RECORDS = "wal_registry_records"
WAL_REGISTRY_ABORTS = "wal_registry_aborts"

# ---- topic router (runtime.replication.TopicRouter) ------------------------
ROUTER_ROUTED = "router_routed"
#: per-reason rejection family: ``router_rejected_<reason>``
ROUTER_REJECTED_PREFIX = "router_rejected_"
ROUTER_BUDGET_SPILLS = "router_budget_spills"
ROUTER_FAILOVERS = "router_failovers"
ROUTER_RECOVERIES = "router_recoveries"
#: a replica cordoned (excluded from rendezvous) for a planned drain —
#: the cutover re-anchor path; distinct from health failover.
ROUTER_CUTOVER_DRAINS = "router_cutover_drains"
ROUTER_HEALTH_PROBE_FAILURES = "router_health_probe_failures"
#: consecutive-probe-exception accounting (ISSUE 16): every probe raise
#: increments this, but the per-replica streak is capped and the warn log
#: fires once per into-erroring transition — a permanently-raising probe
#: is one log line, not one per cycle.
ROUTER_PROBE_ERRORS = "router_probe_errors"
ROUTER_REPLICAS = "router_replicas"
ROUTER_HEALTHY_REPLICAS = "router_healthy_replicas"
#: interactive-priority hedged dispatch (ISSUE 16): re-sends of an
#: interactive frame to the next rendezvous-preferred replica after the
#: hedge deadline; ``wins`` = the hedged copy's result arrived first,
#: ``wasted`` = the original won and the hedge's result was deduped.
ROUTER_HEDGES = "router_hedges"
ROUTER_HEDGE_WINS = "router_hedge_wins"
ROUTER_HEDGE_WASTED = "router_hedge_wasted"

# ---- supervisor ------------------------------------------------------------
SUPERVISOR_CHECKPOINTS = "supervisor_checkpoints"
SUPERVISOR_RESTARTS = "supervisor_restarts"
SUPERVISOR_STALLS = "supervisor_stalls"
SUPERVISOR_GAVE_UP = "supervisor_gave_up"
SUPERVISOR_DURABLE_RESTORES = "supervisor_durable_restores"


# ---- ledger source-of-truth tables (ocvf-lint ledger-registry-coherence) ---
# The admission-ledger invariant is
#   admitted == Σ(LEDGER_COMPLETION_COUNTERS) + Σ(LEDGER_DROP_COUNTERS)
# at quiescence.  These two tables are THE definition of "terminal status":
# the runtime (RecognizerService.ledger/frames_in_system), the span reducer
# (tracing.account_spans), the chaos soak's span mirror, and the settle-once
# lint rule all derive from them.  A new terminal bucket starts here; the
# ledger-registry-coherence rule flags every mirror site that missed it.
LEDGER_COMPLETION_COUNTERS = (
    FRAMES_COMPLETED,
    FRAMES_COMPLETED_EMPTY,
    FRAMES_COMPLETED_CACHED,
)
LEDGER_DROP_COUNTERS = (
    FRAMES_MALFORMED,
    FRAMES_DROPPED_DECODE,
    BATCHER_DROPPED_MALFORMED,
    BATCHER_DROPPED_OVERFLOW,
    BATCHER_DROPPED_STALE,
    BATCHER_DROPPED_CLOSED,
    FRAMES_DROPPED_BROWNOUT,
    FRAMES_DEAD_LETTERED,
    FRAMES_FAILED,
    FRAMES_DROPPED_CRASHED,
)

#: The dynamic prefix families promtext folds into labeled Prometheus
#: families.  promtext._LABEL_FAMILIES must mirror this set exactly.
PROM_FOLDED_PREFIXES = (
    FRAMES_REJECTED_PREFIX,
    BATCHER_DROPPED_PREFIX,
    SLO_EVENTS_PREFIX,
    SLO_BURN_PREFIX,
    TRACK_FLUSHES_PREFIX,
    TRANSPORT_FAULTS_PREFIX,
    ROUTER_REJECTED_PREFIX,
)


def all_names():
    """Every registered full metric name (prefix families excluded) —
    used by tests to assert the registry has no duplicate values."""
    return sorted(v for k, v in globals().items()
                  if k.isupper() and not k.endswith("_PREFIX")
                  and isinstance(v, str))


def all_prefixes():
    return sorted(v for k, v in globals().items()
                  if k.endswith("_PREFIX") and isinstance(v, str))

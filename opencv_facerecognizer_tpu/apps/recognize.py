"""``ocvf-recognize``: the live recognizer node (SURVEY.md §2.1 "Standalone
recognizer app" / "ROS recognizer node", rebuilt per §3.3): frames in ->
fused TPU batch recognition -> results out.

Transports:
- ``--source jsonl`` (default): frames as JSONL on stdin (see
  runtime.connector.encode_frame for the schema), results as JSONL on
  stdout — the shippable default in a ROS-less environment. The enrolment
  protocol rides the same stream ({"topic": "ocvfacerec/control",
  "data": {"cmd": "enroll", ...}}).
- ``--source dir``: replay a directory of images once and exit — demo/
  verification mode.

Needs a CNN embedding model checkpoint (ocvf-train --model cnn) and a
detector checkpoint (CNNFaceDetector.save).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="ocvf-recognize",
                                description="Live face recognition on TPU")
    # Required for every SERVING mode; the offline --registry-swap
    # runbook touches only the state dir and needs none of them, so the
    # requirement is enforced in main() rather than by argparse.
    p.add_argument("--model", help="CNN model checkpoint (ocvf-train --model cnn)")
    p.add_argument("--detector", help="detector checkpoint (CNNFaceDetector.save)")
    p.add_argument("--gallery",
                   help="dataset dir to enroll at startup (folder per subject)")
    p.add_argument("--source", choices=["jsonl", "socket", "dir"], default="jsonl")
    p.add_argument("--dir", help="image directory for --source dir")
    p.add_argument("--port", type=int, default=5600,
                   help="TCP port for --source socket (JSONL over TCP)")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address for --source socket")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler trace of the first "
                        "--profile-batches batches into this directory "
                        "(open with TensorBoard or xprof)")
    p.add_argument("--profile-batches", type=int, default=20)
    p.add_argument("--frame-size", type=int, nargs=2, default=(256, 256), metavar=("H", "W"))
    p.add_argument("--parallel", choices=["fused", "pp"], default="fused",
                   help="fused: one sharded graph over all devices (default); "
                        "pp: two-stage pipeline parallelism — detector on "
                        "half the devices, embedder+gallery on the other "
                        "half (needs an even device count >= 2)")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--flush-ms", type=float, default=30.0,
                   help="max age of the oldest buffered frame before a "
                        "partial batch flushes; with --target-latency-ms "
                        "this is the CAP of the adaptive deadline")
    # ---- overlapped serving pipeline (runtime.recognizer docstring) ----
    p.add_argument("--target-latency-ms", type=float, default=None,
                   help="continuous-batching latency target: a partial "
                        "batch waits only target minus the EWMA of the "
                        "measured downstream service time (clamped to "
                        "[2 ms, --flush-ms]) instead of the fixed flush "
                        "window — trickle load stops paying the full "
                        "--flush-ms of batching delay")
    p.add_argument("--bucket-sizes", type=int, nargs="+",
                   default=[8, 32, 128], metavar="B",
                   help="dispatch bucket ladder: a partial batch is sliced "
                        "to the smallest bucket >= its real frame count "
                        "(every bucket is compiled at warmup, so partial "
                        "batches never recompile); 0 disables slicing")
    # ---- ingest pipeline (runtime.ingest; README "Ingest pipeline") ----
    p.add_argument("--ingest-mode", choices=["f32", "uint8", "jpeg"],
                   default="f32",
                   help="ingest transfer mode. f32 (default): legacy "
                        "float staging. uint8: frames stage and cross "
                        "host->device as uint8 through the pre-allocated "
                        "staging ring (4x less transfer volume; the cast/"
                        "normalize fuses into the detect prologue on "
                        "device). jpeg: uint8 plus compressed camera "
                        "payloads ({'__jpeg__': base64}) decoded off the "
                        "hot thread by the decode worker pool directly "
                        "into the staging ring")
    p.add_argument("--ingest-ring-depth", type=int, default=0,
                   help="staging buffers pre-allocated per dispatch-"
                        "bucket rung. 0 (default) = auto: sized to the "
                        "in-flight window + 2 so the bounded ring never "
                        "caps pipeline overlap (every overlapped batch "
                        "holds a buffer, plus the one being assembled). "
                        "Ring exhaustion backpressures through admission "
                        "(reason=staging), never allocates")
    p.add_argument("--ingest-decode-workers", type=int, default=2,
                   help="decode worker threads for --ingest-mode jpeg "
                        "(corrupt payloads dead-letter with reason "
                        "decode_error; depth/latency on the metrics "
                        "surface)")
    # ---- cascade early-exit detection (models.cascade; README) ----
    p.add_argument("--cascade", metavar="PATH",
                   help="stage-1 FaceGate checkpoint (models.cascade."
                        "FaceGate.save): score every frame at reduced "
                        "resolution first and dispatch only face-possible "
                        "frames to the full detector; face-free frames "
                        "settle as completed_empty with an empty result "
                        "publish. Unset = single-stage serving")
    p.add_argument("--cascade-threshold", type=float, default=None,
                   metavar="P",
                   help="stage-1 operating point: frames scoring below P "
                        "exit early. Default: the checkpoint's own trained "
                        "threshold. Brownout level >= 1 tightens it one "
                        "notch (rejecting borderline frames) before "
                        "shedding admitted intake")
    p.add_argument("--no-cascade", action="store_true",
                   help="escape hatch: serve single-stage even with a "
                        "--cascade checkpoint loaded (e.g. to A/B the "
                        "gate's recall in production)")
    # ---- temporal identity cache (runtime.tracker; README) ----
    p.add_argument("--track-reverify-frames", type=int, default=8,
                   metavar="N",
                   help="temporal identity cache: a track whose stream "
                        "stays coherent serves its confirmed identity "
                        "from the cache (frames settle completed_cached, "
                        "skipping detect+embed+match) for at most N-1 "
                        "consecutive frames before a scheduled full "
                        "re-verify; appearance drift or association "
                        "ambiguity re-verifies immediately. Brownout "
                        "level >= 1 stretches the interval before "
                        "shedding intake")
    p.add_argument("--track-iou-min", type=float, default=0.3,
                   metavar="IOU",
                   help="minimum box IoU for frame-to-frame track "
                        "association (centroid fallback below it)")
    p.add_argument("--no-track-cache", action="store_true",
                   help="escape hatch: disable the temporal identity "
                        "cache — every frame takes the full "
                        "detect+embed+match path")
    p.add_argument("--similarity-threshold", type=float, default=0.3)
    p.add_argument("--capacity", type=int, default=4096, help="gallery capacity")
    p.add_argument("--gallery-dtype", choices=["bf16", "f32"], default="bf16",
                   help="device dtype of gallery rows. bf16 (default): half "
                        "the gallery HBM and upload bytes (match speed on "
                        "the local chip: not measured), "
                        "numerically identical — both matchers compute "
                        "bf16 x bf16 -> f32 regardless of storage")
    # ---- large-gallery matching (parallel.quantizer / ops.ivf_match;
    # README "Large-gallery matching") ----
    p.add_argument("--match-mode", choices=["auto", "exact", "ivf"],
                   default="auto",
                   help="gallery matcher selection. auto (default): exact "
                        "scan below the IVF capacity threshold (262k "
                        "rows), two-stage IVF shortlist + exact rerank "
                        "above it; exact: always brute-force; ivf: "
                        "two-stage whenever the quantizer is trained "
                        "(falls back to exact until then). The exact "
                        "scan is linear in gallery size — million-"
                        "identity galleries need ivf/auto")
    p.add_argument("--ivf-nlist", type=int, default=0,
                   help="k-means cell count of the IVF coarse quantizer; "
                        "0 = auto (~4*sqrt(capacity), power of two). More "
                        "cells = smaller rerank buckets but a costlier "
                        "stage-1 scan and retrain")
    p.add_argument("--ivf-nprobe", type=int, default=8,
                   help="shortlisted cells per query: the recall-vs-"
                        "latency knob (each probe adds one cell's rows "
                        "to the exact rerank bucket)")
    p.add_argument("--async-grow", action="store_true",
                   help="gallery auto-grow compiles + installs the next "
                        "tier on a background thread: overflowing "
                        "enrolments return immediately and become "
                        "matchable seconds later, instead of stalling the "
                        "serving loop for the XLA recompile")
    p.add_argument("--metrics-jsonl", help="append per-batch metrics to this file")
    # ---- steady-state failure handling (runtime.resilience) ----
    p.add_argument("--readback-deadline", type=float, default=30.0,
                   metavar="S",
                   help="dead-letter a dispatched batch whose device->host "
                        "readback is not ready after this many seconds "
                        "(the hang-mode outage costs one deadline, never "
                        "a wedge)")
    p.add_argument("--dispatch-retries", type=int, default=3,
                   help="retries per batch on transient (outage-shaped) "
                        "dispatch failures, with exponential backoff")
    p.add_argument("--degraded-after", type=int, default=3,
                   help="consecutive dispatch failures before the service "
                        "publishes degraded mode on the status topic and "
                        "(with --probe-on-degraded) checks the backend")
    p.add_argument("--probe-on-degraded", action="store_true",
                   help="on entering degraded mode, run the deadline-"
                        "bounded in-process device probe and attach its "
                        "verdict to the status message; a dead device then "
                        "rebuilds the pipeline on the host CPU (explicit, "
                        "announced)")
    p.add_argument("--supervised", action="store_true",
                   help="wrap the service in a ServiceSupervisor: a crash "
                        "that kills the serving loop is restarted with "
                        "the last-known-good gallery snapshot (bounded "
                        "restarts)")
    # ---- overload protection (runtime.admission / README section) ----
    p.add_argument("--max-inflight-frames", type=int, default=0,
                   help="admission bound: reject new frames (explicit "
                        "'rejected' status, reason=overload) once this "
                        "many admitted frames are still in the system; "
                        "bulk-priority frames are rejected at 75%% of the "
                        "bound so interactive traffic keeps headroom. "
                        "0 = unbounded")
    p.add_argument("--rate-limit-fps", type=float, default=0.0,
                   help="per-topic token-bucket rate limit (frames/s, "
                        "burst = 1 s of rate): producers above it get "
                        "explicit 'rejected' statuses (reason=rate_limit) "
                        "instead of silently displacing queued frames. "
                        "0 = off")
    p.add_argument("--brownout-queue-wait-ms", type=float, default=0.0,
                   help="brownout threshold: when the queue-wait EWMA "
                        "crosses this, degrade work per frame (level 1: "
                        "skip-shed half the bulk frames; level 2: shed "
                        "all bulk + cap the dispatch ladder at its "
                        "smallest bucket), announced on the status topic "
                        "with a brownout_level gauge and automatic "
                        "hysteresis recovery. 0 = off")
    p.add_argument("--shed-stale-after-ms", type=float, default=0.0,
                   help="freshness bound: a queued frame older than this "
                        "is shed (reason=stale) instead of wasting a "
                        "dispatch slot. 0 = off")
    p.add_argument("--dead-letter-journal", metavar="PATH",
                   help="append dead-lettered/shed frame metadata + "
                        "reason to this bounded rotating JSONL journal "
                        "(replayable: python -m opencv_facerecognizer_tpu"
                        ".runtime.journal PATH)")
    # ---- crash-safe state lifecycle (runtime.state_store / README
    # "State durability") ----
    p.add_argument("--state-dir", metavar="DIR",
                   help="durable state directory: atomic checksummed "
                        "gallery checkpoints + an enrollment write-ahead "
                        "log. On startup the newest verified checkpoint "
                        "is restored and the WAL replayed (superseding "
                        "the --gallery startup enrollment); enrollments "
                        "accepted while serving then survive restarts. "
                        "Unset = state lives only in memory")
    p.add_argument("--embedder-version", type=int, default=0, metavar="N",
                   help="declare the loaded --model's embedder version "
                        "(rollout fencing; README 'Live embedder "
                        "rollout'). 0 (default) = adopt whatever version "
                        "the state dir's newest checkpoint carries. "
                        "Nonzero: startup FAILS CLOSED when the recovered "
                        "state serves a different version — a new "
                        "embedder's rows must arrive via the staged "
                        "re-embed cutover (or this binary must complete a "
                        "pending one), never by silently mixing spaces")
    p.add_argument("--detector-version", type=int, default=0, metavar="N",
                   help="declare the loaded --detector's registry version "
                        "(model-registry fencing; README 'Model "
                        "registry'). 0 (default) = adopt whatever the "
                        "state dir's manifest serves. Nonzero: startup "
                        "FAILS CLOSED — writer and reader both — when the "
                        "manifest serves a different detector version; a "
                        "new detector arrives via the fenced registry "
                        "swap, never by silently starting a different "
                        "checkpoint")
    p.add_argument("--cascade-version", type=int, default=0, metavar="N",
                   help="declare the loaded --cascade stage-1 gate's "
                        "registry version: same fail-closed startup fence "
                        "as --detector-version, for the cascade role")
    p.add_argument("--registry-swap", metavar="ROLE=VERSION",
                   help="runbook entry point: perform ONE fenced model-"
                        "registry swap against --state-dir and exit. The "
                        "candidate params must already be staged at the "
                        "registry convention path (state_dir/registry/"
                        "<role>-v<N>.params); the swap appends the WAL "
                        "fence, installs the manifest atomically, and "
                        "exits 0 — serving writers pick the new version "
                        "up at their next startup fence, readers across "
                        "their next re-anchor. Roles: detector, cascade")
    p.add_argument("--checkpoint-every-s", type=float, default=300.0,
                   help="age threshold for background checkpoints: WAL "
                        "entries older than this trigger one (only "
                        "meaningful with --state-dir)")
    p.add_argument("--checkpoint-wal-rows", type=int, default=256,
                   help="row-count threshold: a WAL holding this many "
                        "enrolled rows triggers a background checkpoint")
    p.add_argument("--keep-checkpoints", type=int, default=3,
                   help="checkpoint retention: newest N kept; older ones "
                        "(and quarantined corrupt files beyond N) pruned")
    p.add_argument("--disk-low-watermark", type=float, default=256.0,
                   metavar="MB",
                   help="disk-pressure low watermark on the --state-dir "
                        "volume (MB free; README 'Degraded-durability "
                        "runbook'). Below it: one preemptive WAL "
                        "compaction (forced checkpoint) + retention "
                        "shrink per pressure episode, and the "
                        "disk_free SLO burns >= 1 (warn). Below "
                        "watermark/6: durability flips to degraded "
                        "BEFORE ENOSPC ever lands (enrollments refused "
                        "closed, serving continues). 0 disables the "
                        "watermark (the WAL-failure trigger stays armed)")
    p.add_argument("--durability-probe-s", type=float, default=5.0,
                   help="degraded-durability recovery probe cadence: "
                        "every N seconds the monitor durably writes + "
                        "fsyncs + unlinks a tmp file in --state-dir; a "
                        "success re-arms durability with a "
                        "durability_restored announcement. Also the "
                        "disk-watermark refresh interval")
    p.add_argument("--journal-fsync", choices=["never", "interval", "always"],
                   default="never",
                   help="fsync policy of the dead-letter journal: never "
                        "(default — flush per record, the original "
                        "behavior), interval (fsync at most once per "
                        "second), always (fsync per record). The "
                        "enrollment WAL always runs at 'always' — its "
                        "acknowledgments promise durability")
    # ---- frame-lifecycle tracing / flight recorder / exposition
    # (utils.tracing, runtime.expo; README "Observability") ----
    p.add_argument("--trace-sample", type=float, default=0.0,
                   help="frame-trace sampling rate in [0, 1]: each sampled "
                        "frame records causal spans (receive -> queue_wait "
                        "-> settle, with batch ancestry) into bounded "
                        "per-topic ring buffers. Deterministic per trace "
                        "id. 0 (default) = frame tracing off; lifecycle "
                        "spans (checkpoint/WAL/retrain/brownout) are "
                        "always recorded once a tracer exists")
    p.add_argument("--trace-ring", type=int, default=4096,
                   help="spans kept per topic ring (the flight recorder's "
                        "horizon)")
    p.add_argument("--trace-jsonl", metavar="PATH",
                   help="additionally stream every span as JSONL into this "
                        "bounded rotating file (offline analysis beyond "
                        "the ring horizon; adds a file write per span)")
    p.add_argument("--flight-dir", metavar="DIR",
                   help="flight-recorder dump directory: the span rings "
                        "are dumped atomically here on dead-letter, "
                        "supervisor restart, wedge detection, and SIGTERM "
                        "drain (bounded retention; dump path rides the "
                        "dead-letter journal record)")
    p.add_argument("--expo-port", type=int, default=None, metavar="PORT",
                   help="serve the read-only observability endpoint "
                        "(GET /metrics /prom /health /ledger /brownout "
                        "/spans) on this TCP port; 0 binds "
                        "an ephemeral port (printed on stderr). Off-hot-"
                        "path threads; unset = off. /prom is Prometheus "
                        "text format; /health is the SLO verdict (503 "
                        "when critical)")
    # ---- SLO burn-rate monitor (runtime.slo; README "Observability") ----
    p.add_argument("--slo", action="store_true",
                   help="run the SLO burn-rate monitor: interactive e2e "
                        "p99, queue-wait p99, ledger completion ratio and "
                        "(with --state-dir) durability lag evaluated on "
                        "multi-window burn rates into an ok/warn/critical "
                        "health state machine — served at /health, "
                        "published on the status topic by the supervisor, "
                        "consumed by brownout as intake pressure at "
                        "critical, and dumped to the flight recorder on a "
                        "critical transition")
    p.add_argument("--slo-interval-s", type=float, default=5.0,
                   help="seconds between SLO evaluations (the serving "
                        "loop's tick cadence; the expo refresh thread "
                        "backstops it when the loop wedges)")
    p.add_argument("--slo-e2e-p99-ms", type=float, default=500.0,
                   help="interactive end-to-end latency objective: 99%% "
                        "of interactive frames must publish within this "
                        "(the error budget is the other 1%%)")
    p.add_argument("--slo-queue-wait-p99-ms", type=float, default=250.0,
                   help="queue-wait objective: 99%% of frames must leave "
                        "the batcher queue within this")
    p.add_argument("--slo-completion-target", type=float, default=0.999,
                   help="completion-ratio objective: the target fraction "
                        "of admitted frames that must publish (drops burn "
                        "the remaining budget)")
    p.add_argument("--slo-durability-rows", type=int, default=1024,
                   help="durability-lag objective bound: WAL rows not yet "
                        "covered by a checkpoint (wal_seq minus the last "
                        "checkpoint's seq) above this read as burn >= 1; "
                        "needs --state-dir")
    p.add_argument("--slo-windows", type=float, nargs=2,
                   default=(60.0, 600.0), metavar=("SHORT_S", "LONG_S"),
                   help="the two burn-rate windows (seconds): a severity "
                        "fires only when BOTH windows burn past its rate "
                        "(short reacts, long filters blips)")
    # ---- multi-replica serving (runtime.replication; README
    # "Horizontal scale-out") ----
    p.add_argument("--replica-role", choices=["writer", "reader"],
                   default="writer",
                   help="role against a shared --state-dir. writer "
                        "(default): owns enrollment — acquires the fcntl "
                        "writer lease in the state dir and FAILS CLOSED "
                        "when another live writer holds it (split-brain "
                        "protection). reader: opens the WAL strictly "
                        "read-only, anchors on the newest checkpoint, and "
                        "tails new enrollment rows between batches; "
                        "enroll commands are rejected with an explicit "
                        "status. Only meaningful with --state-dir")
    p.add_argument("--replica-poll-ms", type=float, default=50.0,
                   help="reader role: WAL tail poll interval — bounds "
                        "replication staleness (plus append visibility) "
                        "per replica")
    p.add_argument("--replication-lag-rows", type=int, default=4096,
                   help="reader role with --slo: replication-lag gauge "
                        "objective bound — unapplied WAL rows above this "
                        "read as burn >= 1 (warn; critical at 6x feeds "
                        "one level of brownout intake pressure)")
    p.add_argument("--router", metavar="HOST:PORT[,HOST:PORT...]",
                   help="run as a model-free TOPIC ROUTER instead of a "
                        "recognizer: frames arriving on --source are "
                        "spread across these replica endpoints (JSONL "
                        "over TCP, i.e. each replica runs --source "
                        "socket) by rendezvous-hashing their topic, with "
                        "health-based failover; results/status fan back "
                        "to the source. All model/gallery flags are "
                        "ignored in this mode")
    p.add_argument("--router-health", metavar="URL[,URL...]",
                   help="per-replica /health URLs (same order as "
                        "--router): 503/unreachable marks the replica "
                        "critical and reroutes its topics. Unset = "
                        "replicas are assumed healthy")
    p.add_argument("--router-budget-fps", type=float, default=0.0,
                   help="per-replica admission budget (frames/s token "
                        "bucket): an over-budget topic spills to its "
                        "next-preferred replica instead of overrunning "
                        "one. 0 = unbudgeted")
    p.add_argument("--router-writer", type=int, default=0, metavar="IDX",
                   help="index (into --router) of the replica that owns "
                        "enrollment: control-topic traffic routes only "
                        "there")
    p.add_argument("--router-link-deadline-s", type=float, default=0.0,
                   help="link supervision: app-level heartbeat (ping/pong "
                        "over the data link itself) per replica per health "
                        "cycle; a pong older than this marks the LINK down "
                        "— routing excludes it and the flight recorder "
                        "dumps a failover — independent of /health, which "
                        "a partition can leave green. 0 = off")
    p.add_argument("--router-hedge-deadline-s", type=float, default=0.0,
                   help="interactive hedging: an interactive frame with no "
                        "result after this many seconds is re-sent once to "
                        "the next-preferred replica (same frame id — the "
                        "loser's result is deduped at fan-in). 0 = off")
    p.add_argument("--router-dedup-window", type=int, default=4096,
                   help="idempotent fan-in: remember this many recent "
                        "frame ids at the router's result intake so a "
                        "duplicated or hedged result publishes upstream "
                        "exactly once (replica intake keeps its own "
                        "window). 0 = off")
    p.add_argument("--slo-loop-stale-s", type=float, default=30.0,
                   help="loop-liveness objective bound: seconds without a "
                        "serving-loop iteration before the gauge reads "
                        "burn >= 1 (warn; critical at 6x). A wedged loop "
                        "produces no latency/ratio events, so only this "
                        "gauge — evaluated by the expo backstop thread — "
                        "can escalate it. 0 = off")
    return p


def _load_stack(args, mesh=None):
    """Checkpoints + startup gallery -> (pipeline, subject names).
    ``mesh`` overrides the default (every visible device on ``tp``) for
    callers that place the fused path themselves (``chip_smoke.py`` pins
    its kernel phases to one device so they mean the same thing on a
    one-chip and a four-chip host)."""
    import numpy as np

    from opencv_facerecognizer_tpu.models.scrfd import load_detector
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.utils import dataset as dataset_utils
    from opencv_facerecognizer_tpu.utils import serialization

    # Pure argument validation FIRST — before checkpoint loads and the
    # full gallery embedding pass, which can take minutes.
    if args.match_mode == "ivf" and args.parallel == "pp":
        raise SystemExit("--match-mode ivf applies to --parallel fused only "
                         "(the two-stage path is single-device, like the "
                         "pallas streaming matcher)")
    if args.cascade and args.parallel == "pp":
        raise SystemExit("--cascade applies to --parallel fused only (the "
                         "pipeline-parallel path carries no stage-1 gate)")

    model = serialization.load_model(args.model)
    feature = model.feature
    # what the step needs of a feature, whatever its class: a net with a
    # flax-style ``apply`` (``parallel.pipeline.EmbedNet``), its parameters
    # and the crop size it takes
    if not (callable(getattr(getattr(feature, "net", None), "apply", None))
            and "net" in (getattr(feature, "_params", None) or {})
            and len(getattr(feature, "input_size", ())) == 2):
        raise SystemExit(
            f"--model must be an embedder checkpoint: a feature with a net "
            f"to apply, its parameters and an input_size (ocvf-train --model "
            f"cnn writes one); {args.model} holds a "
            f"{getattr(feature, 'name', type(feature).__name__)!r} feature")
    # either detector class, by the checkpoint's header (a CNNFaceDetector
    # file names no kind, an SCRFD one names its own)
    detector = load_detector(args.detector)
    face_gate = None
    if args.cascade:
        from opencv_facerecognizer_tpu.models.cascade import FaceGate

        face_gate = FaceGate.load(args.cascade)

    images, labels, names = dataset_utils.read_images(
        args.gallery, image_size=feature.input_size
    )
    emb = np.array(feature.extract(images))
    mesh_a = None
    if args.parallel == "pp":
        # Two-stage pipeline parallelism: detector on the first mesh half,
        # embedder + gallery on the second (parallel/pp.py).
        import jax

        from opencv_facerecognizer_tpu.parallel import split_mesh

        n = len(jax.devices())
        # Keep both axes useful after the split: 8 devices -> (dp=4, tp=2)
        # halves into two (2, 2) stage meshes. Below 8, tp=2 would collapse
        # the halves to dp=1 (replicated detector work), so stay tp=1.
        tp = 2 if n % 4 == 0 and n >= 8 else 1
        try:
            mesh_a, gallery_mesh = split_mesh(make_mesh(dp=n // tp, tp=tp))
        except ValueError as e:
            raise SystemExit(
                f"--parallel pp needs an even device count >= 2 (have {n}): "
                f"{e}; use --parallel fused on this host"
            )
    else:
        gallery_mesh = mesh if mesh is not None else make_mesh()

    import jax.numpy as jnp

    gallery = ShardedGallery(capacity=max(args.capacity, 2 * len(emb)),
                             dim=emb.shape[1], mesh=gallery_mesh,
                             async_grow=args.async_grow,
                             store_dtype=(jnp.bfloat16
                                          if args.gallery_dtype == "bf16"
                                          else jnp.float32),
                             embedder_version=args.embedder_version or 1)
    gallery.add(emb, labels)  # ocvf-lint: boundary=wal-before-mutate -- startup ingest of the model's frozen subject set, BEFORE recovery/serving; durable enrollments arrive later via StateLifecycle replay
    if args.match_mode == "ivf" and gallery_mesh.size > 1:
        # Fail fast, like the pp guard above: the two-stage path is
        # single-device (GSPMD cannot partition the bucket gather +
        # pallas rerank), and silently serving the linear exact scan
        # under an explicit --match-mode ivf would blow the very
        # deadlines the flag exists to protect.
        raise SystemExit("--match-mode ivf requires a single-device mesh "
                         f"(got {gallery_mesh.size} devices); use "
                         "--match-mode auto/exact on this host")
    if (args.match_mode != "exact" and mesh_a is None
            and gallery_mesh.size == 1):
        # Attach the IVF coarse quantizer AFTER the startup enrolment:
        # pre-build incremental assignment is a no-op, and attaching late
        # keeps the one explicit startup build (main(), post state
        # recovery) from racing an add-triggered background one.
        from opencv_facerecognizer_tpu.parallel.quantizer import CoarseQuantizer

        gallery.attach_quantizer(
            CoarseQuantizer(
                nlist=(args.ivf_nlist
                       or CoarseQuantizer.default_nlist(gallery.capacity)),
                nprobe=args.ivf_nprobe,
                # --ivf-nlist 0: re-derive the cell count from the actual
                # row set at every (re)build — state recovery or runtime
                # growth must not freeze the startup capacity guess.
                auto_nlist=not args.ivf_nlist,
            ),
            mode=args.match_mode,
        )
    if mesh_a is not None:
        from opencv_facerecognizer_tpu.parallel import TwoStagePipeline

        pipeline = TwoStagePipeline(
            detector, feature.net, feature._params["net"], gallery, mesh_a,
            face_size=feature.input_size,
        )
    else:
        import jax

        # Buffer donation through the bucketed ladder: only when the
        # ingest uploader feeds each dispatch a fresh device array AND
        # the backend implements input donation (CPU ignores it with a
        # warning per compiled step — noise, not a win).
        donate = (args.ingest_mode != "f32"
                  and jax.devices()[0].platform in ("tpu", "gpu"))
        pipeline = RecognitionPipeline(
            detector, feature.net, feature._params["net"], gallery,
            face_size=feature.input_size,
            donate_frames=donate,
            cascade=face_gate,
        )
    return pipeline, names


def train_quantizer_if_wanted(gallery) -> None:
    """Train the IVF shortlist before serving starts when the gallery's
    tier wants it and no build has published yet (sidecar missed, or no
    ``--state-dir``) — predictable startup beats a recall-free window.
    ``skip_if_ready`` rides out the background build an enrolment or
    recovery poke may already have fired instead of training twice.
    ``--match-mode auto`` below the capacity threshold skips this and
    lets the staleness poke build it if the gallery ever grows there."""
    quantizer = getattr(gallery, "quantizer", None)
    if quantizer is None or quantizer.ready or not gallery._ivf_wanted():
        return
    print(f"training IVF coarse quantizer (nlist={quantizer.nlist})...",
          file=sys.stderr)
    quantizer.rebuild_now(wait=True, skip_if_ready=True)
    print(f"IVF quantizer: {quantizer.stats()}", file=sys.stderr)


def build_service(args, pipeline, names, connector, metrics, *,
                  admission=None, brownout=None, journal=None, state=None,
                  tracer=None, slo_monitor=None, replica=None):
    """The ``RecognizerService`` exactly as ``main`` wires it from the
    parsed flags (tracker, cascade, ingest, ladder, resilience policy) —
    one construction site, so ``chip_smoke.py`` serves through the same
    wiring the CLI does instead of a copy of it."""
    from opencv_facerecognizer_tpu.runtime.ingest import IngestConfig
    from opencv_facerecognizer_tpu.runtime.recognizer import RecognizerService
    from opencv_facerecognizer_tpu.runtime.resilience import (
        ResiliencePolicy, rebuild_pipeline_on_cpu,
    )

    gallery = getattr(pipeline, "gallery", None)
    if hasattr(gallery, "attach_observability"):
        gallery.attach_observability(metrics, tracer)

    ingest_cfg = IngestConfig(
        mode=args.ingest_mode,
        ring_depth=args.ingest_ring_depth or None,
        decode_workers=args.ingest_decode_workers)

    tracker = None
    if not args.no_track_cache:
        from opencv_facerecognizer_tpu.runtime.tracker import (
            IdentityTracker, TrackerConfig,
        )

        # Replica-local by construction: the tracker lives on THIS
        # service instance, and PR 10's rendezvous routing pins each
        # topic to one replica — failover/resync lands on a replica
        # whose cache simply starts cold.
        tracker = IdentityTracker(
            TrackerConfig(reverify_frames=max(1, args.track_reverify_frames),
                          iou_min=args.track_iou_min),
            metrics=metrics)

    return RecognizerService(
        pipeline, connector,
        batch_size=args.batch_size,
        frame_shape=tuple(args.frame_size),
        flush_timeout=args.flush_ms / 1e3,
        similarity_threshold=args.similarity_threshold,
        subject_names=names,
        metrics=metrics,
        # The ingest config owns the transfer dtype now (uint8/jpeg stage
        # as uint8 through the ring; f32 keeps the legacy dtype).
        ingest=ingest_cfg,
        bucket_sizes=tuple(b for b in args.bucket_sizes if b > 0),
        target_latency_s=(None if args.target_latency_ms is None
                          else args.target_latency_ms / 1e3),
        admission=admission,
        brownout=brownout,
        dead_letter_journal=journal,
        shed_stale_after_s=(args.shed_stale_after_ms / 1e3
                            if args.shed_stale_after_ms > 0 else None),
        state_store=state,
        resilience=ResiliencePolicy(
            dispatch_retries=args.dispatch_retries,
            readback_deadline_s=args.readback_deadline,
            degraded_after=args.degraded_after,
            probe_backend_on_degraded=args.probe_on_degraded,
        ),
        # Dead accelerator -> rebuild the pipeline on host devices: the
        # job degrades to CPU speed instead of wedging (README "Failure
        # handling"). Only reachable with --probe-on-degraded.
        cpu_fallback=rebuild_pipeline_on_cpu if args.probe_on_degraded else None,
        tracer=tracer,
        slo_monitor=slo_monitor,
        replica=replica,
        cascade=not args.no_cascade,
        cascade_threshold=args.cascade_threshold,
        tracker=tracker,
    )


def run_router(args) -> int:
    """Model-free router mode (``--router``): spread incoming camera
    topics across replica endpoints with rendezvous hashing + health
    failover (``runtime.replication.TopicRouter``), fanning results and
    statuses back to the source. No model, no gallery, no device — the
    whole process is transport + routing, so it starts in milliseconds
    and can sit in front of replicas on other hosts."""
    import signal
    import threading

    from opencv_facerecognizer_tpu.runtime.connector import (
        WILDCARD_TOPIC, JSONLConnector, SocketConnector,
    )
    from opencv_facerecognizer_tpu.runtime.recognizer import (
        RESULT_TOPIC, STATUS_TOPIC,
    )
    from opencv_facerecognizer_tpu.runtime.replication import (
        ReplicaHandle, TopicRouter, http_health_probe,
    )
    from opencv_facerecognizer_tpu.utils.metrics import Metrics
    from opencv_facerecognizer_tpu.utils.tracing import Tracer

    metrics = Metrics()
    tracer = None
    if args.flight_dir or args.expo_port is not None:
        tracer = Tracer(ring_size=args.trace_ring, sample=args.trace_sample,
                        dump_dir=args.flight_dir, metrics=metrics)
    endpoints = [e.strip() for e in args.router.split(",") if e.strip()]
    healths = ([u.strip() or None for u in args.router_health.split(",")]
               if args.router_health else [None] * len(endpoints))
    if len(healths) != len(endpoints):
        raise SystemExit(f"--router-health lists {len(healths)} URLs for "
                         f"{len(endpoints)} --router endpoints")
    if not 0 <= args.router_writer < len(endpoints):
        raise SystemExit(f"--router-writer {args.router_writer} is out of "
                         f"range for {len(endpoints)} endpoints")
    replicas = []
    for i, endpoint in enumerate(endpoints):
        host, _, port = endpoint.rpartition(":")
        try:
            conn = SocketConnector(host=host or "127.0.0.1", port=int(port),
                                   listen=False, metrics=metrics)
            conn.start()  # a replica that was never there is a config error
        except (OSError, ValueError) as exc:
            raise SystemExit(f"--router endpoint {endpoint!r}: {exc}")
        replicas.append(ReplicaHandle(
            endpoint, conn,
            health_fn=(http_health_probe(healths[i]) if healths[i] else None),
            budget_fps=args.router_budget_fps or None,
            writer=i == args.router_writer))
    router = TopicRouter(
        replicas, metrics=metrics, tracer=tracer,
        link_deadline_s=args.router_link_deadline_s or None,
        hedge_deadline_s=args.router_hedge_deadline_s or None,
        dedup_window=args.router_dedup_window)
    slo_monitor = None
    if args.slo and args.router_link_deadline_s:
        from opencv_facerecognizer_tpu.runtime.slo import (
            SLOMonitor, link_health_objective,
        )

        # The router's /health speaks for the FABRIC, not a model: the
        # only objective that makes sense here is the supervised-link
        # fraction (one dark replica = failover's job, a majority dark
        # = a network event the fleet cannot route around).
        slo_monitor = SLOMonitor(
            metrics, [link_health_objective(router.down_link_fraction)],
            tracer=tracer)
    if args.source == "socket":
        upstream = SocketConnector(host=args.host, port=args.port,
                                   listen=True, metrics=metrics)
    else:
        upstream = JSONLConnector(sys.stdin, sys.stdout, metrics=metrics)
    upstream.subscribe(WILDCARD_TOPIC,
                       lambda topic, msg: router.publish(topic, msg))
    for topic in (RESULT_TOPIC, STATUS_TOPIC):
        upstream_topic = topic
        router.subscribe(topic, lambda _t, msg, _up=upstream_topic:
                         upstream.publish(_up, msg))
    expo = None
    if args.expo_port is not None:
        from opencv_facerecognizer_tpu.runtime.expo import ExpoServer

        expo = ExpoServer(metrics=metrics, tracer=tracer, router=router,
                          slo=slo_monitor, port=args.expo_port)
        expo.start()
        print(f"router expo endpoint: http://{expo.host}:{expo.port}/",
              file=sys.stderr)
    term_event = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda s, f: term_event.set())
    except ValueError:
        pass
    router.start()
    upstream.start()
    print(f"routing {len(replicas)} replicas: {', '.join(endpoints)}",
          file=sys.stderr)
    try:
        while not upstream.eof.wait(timeout=0.5):
            if term_event.is_set():
                break
    except KeyboardInterrupt:
        pass
    finally:
        if expo is not None:
            expo.stop()
        upstream.stop()
        router.stop()
        for handle in replicas:
            handle.connector.stop()
        print(f"router registry at shutdown: "
              f"{[r['name'] for r in router.registry()]}", file=sys.stderr)
    return 0


def _registry_fence(registry, args, who: str) -> None:
    """Fail-closed startup fence for the non-embedder registry roles
    (mirrors the --embedder-version fence): a declared version that the
    state dir's manifest doesn't serve refuses to start — writer AND
    reader — because serving a detector/cascade the manifest doesn't
    name is exactly the silent unfenced swap the registry exists to
    prevent."""
    for role, declared in (("detector", args.detector_version),
                           ("cascade", args.cascade_version)):
        if declared and registry.version(role) != declared:
            raise SystemExit(
                f"ocvf-recognize: --{role}-version {declared} declared "
                f"but the state dir's registry manifest serves {role} "
                f"v{registry.version(role)} — a {who} never serves a "
                f"model set the manifest doesn't name. Swap the {role} "
                f"through the fenced registry (--registry-swap {role}=N "
                f"or the live coordinator), or start the matching "
                f"checkpoint")


def run_registry_swap(args) -> int:
    """One fenced model-registry swap against ``--state-dir``, then exit
    (README "Model registry" runbook): validate the staged candidate
    params at the registry convention path, take the writer lease (a
    live writer must never race the manifest install — drive a swap
    through ITS coordinator instead), append the ``registry_cutover``
    WAL fence and install the manifest atomically. No serving process is
    touched: writers adopt the new version at their next startup fence,
    readers across their next re-anchor."""
    from opencv_facerecognizer_tpu.runtime.registry import (
        ModelRegistry, _file_sha256, registry_params_path,
    )
    from opencv_facerecognizer_tpu.runtime.replication import (
        WriterLease, WriterLeaseHeldError,
    )
    from opencv_facerecognizer_tpu.runtime.state_store import StateLifecycle
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    if not args.state_dir:
        raise SystemExit("ocvf-recognize: --registry-swap requires "
                         "--state-dir")
    role, sep, version = args.registry_swap.partition("=")
    role = role.strip()
    try:
        to_version = int(version)
    except ValueError:
        to_version = 0
    if not sep or role not in ("detector", "cascade") or to_version <= 0:
        raise SystemExit(
            "ocvf-recognize: --registry-swap wants ROLE=VERSION with role "
            "in (detector, cascade) and a positive integer version, got "
            f"{args.registry_swap!r}")
    params_path = registry_params_path(args.state_dir, role, to_version)
    if not os.path.exists(params_path):
        raise SystemExit(
            f"ocvf-recognize: stage the candidate params first — "
            f"{params_path} does not exist (CNNFaceDetector.save / "
            f"FaceGate.save to the registry convention path)")
    metrics = Metrics()
    lease = WriterLease(args.state_dir, metrics=metrics)
    try:
        lease.acquire()
    except WriterLeaseHeldError as exc:
        raise SystemExit(
            f"ocvf-recognize: {exc} — stop the writer first (or drive the "
            f"swap through its live coordinator); the offline runbook swap "
            f"needs exclusive ownership of the state dir")
    try:
        state = StateLifecycle(args.state_dir, metrics=metrics)
        state.attach_registry(ModelRegistry(args.state_dir, metrics=metrics))
        state.adopt_wal_seq()
        try:
            seq = state.perform_registry_cutover(
                role, to_version, params_path=params_path,
                params_sha256=_file_sha256(params_path))
        except ValueError as exc:
            raise SystemExit(f"ocvf-recognize: {exc}")
        print(f"registry swap fenced at WAL seq {seq}; manifest now "
              f"serves {state.registry.stamp()} (readers re-anchor once "
              f"the next writer checkpoint covers the fence)",
              file=sys.stderr)
    finally:
        lease.release()
    return 0


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.registry_swap:
        return run_registry_swap(args)
    if not (args.model and args.detector and args.gallery):
        parser.error("the following arguments are required: --model, "
                     "--detector, --gallery (only --registry-swap runs "
                     "without a serving stack)")
    if args.router:
        return run_router(args)
    from opencv_facerecognizer_tpu.runtime.connector import (
        FakeConnector, JSONLConnector, SocketConnector, encode_frame,
    )
    from opencv_facerecognizer_tpu.runtime.recognizer import (
        FRAME_TOPIC, RESULT_TOPIC, RecognizerService,
    )
    from opencv_facerecognizer_tpu.runtime.admission import AdmissionController
    from opencv_facerecognizer_tpu.runtime.journal import DeadLetterJournal
    from opencv_facerecognizer_tpu.runtime.resilience import (
        BrownoutPolicy, ServiceSupervisor,
    )
    from opencv_facerecognizer_tpu.runtime.state_store import StateLifecycle
    from opencv_facerecognizer_tpu.utils import compile_cache
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    compile_cache.enable()
    pipeline, names = _load_stack(args)
    for line in pipeline.gallery.describe_matchers():
        print(f"ocvf-recognize: {line}", file=sys.stderr)
    metrics_sink = open(args.metrics_jsonl, "a") if args.metrics_jsonl else None
    # The latency rolling horizon must cover the longest SLO evaluation
    # window and the ring resolution must cover the shortest (SLOMonitor
    # refuses both at construction) — a user asking for a 1 h long window
    # gets a 1 h ring, and a 5 s short window gets <=5 s slices, not a
    # silent truncation/dilution of either. Slices are capped: past the
    # cap the monitor's loud constructor names the incompatible pair.
    metrics_window_s, metrics_window_slices = 600.0, 20
    if args.slo:
        import math as _math

        slo_short_s = min(args.slo_windows)
        metrics_window_s = max(metrics_window_s, *args.slo_windows)
        metrics_window_slices = min(960, max(
            20, int(_math.ceil(metrics_window_s
                               / max(1e-3, min(30.0, slo_short_s))))))
    metrics = Metrics(sink=metrics_sink, window_s=metrics_window_s,
                      window_slices=metrics_window_slices)

    # Frame-lifecycle tracer: built whenever ANY observability surface is
    # requested (sampled frame spans, flight dumps, span JSONL, or the
    # expo endpoint — lifecycle spans make the latter two useful even at
    # sample 0). None otherwise: tracing fully off costs nothing.
    from opencv_facerecognizer_tpu.utils.tracing import (
        Tracer, make_span_journal,
    )

    tracer = None
    span_journal = None
    if (args.trace_sample > 0 or args.flight_dir or args.trace_jsonl
            or args.expo_port is not None):
        if args.trace_jsonl:
            span_journal = make_span_journal(args.trace_jsonl,
                                             metrics=metrics)
        tracer = Tracer(ring_size=args.trace_ring,
                        sample=args.trace_sample,
                        dump_dir=args.flight_dir,
                        span_sink=span_journal,
                        metrics=metrics)

    quantizer = getattr(pipeline.gallery, "quantizer", None)
    if quantizer is not None:
        quantizer.metrics = metrics
        quantizer.tracer = tracer

    admission = None
    if args.max_inflight_frames > 0 or args.rate_limit_fps > 0:
        admission = AdmissionController(
            max_inflight_frames=args.max_inflight_frames or None,
            rate_limit_fps=args.rate_limit_fps or None,
        )
    brownout = (BrownoutPolicy(queue_wait_s=args.brownout_queue_wait_ms / 1e3)
                if args.brownout_queue_wait_ms > 0 else None)
    journal = (DeadLetterJournal(args.dead_letter_journal, metrics=metrics,
                                 fsync=args.journal_fsync)
               if args.dead_letter_journal else None)

    state = None
    replica = None
    lease = None
    if args.state_dir and args.replica_role == "reader":
        # Read replica: strictly read-only against the shared state dir —
        # no lease, no WAL writes, no checkpoints. Initial sync anchors
        # on the newest checkpoint and replays the WAL tail; the serving
        # loop then polls for new rows between batches.
        from opencv_facerecognizer_tpu.runtime.replication import ReadReplica

        replica = ReadReplica(args.state_dir, pipeline.gallery, names,
                              metrics=metrics, tracer=tracer,
                              poll_interval_s=args.replica_poll_ms / 1e3)
        report = replica.resync()
        print(f"replica initial sync: {report}", file=sys.stderr)
        if (args.embedder_version
                and replica.embedder_version != args.embedder_version):
            raise SystemExit(
                f"ocvf-recognize: --embedder-version {args.embedder_version}"
                f" declared but the state dir's checkpoint serves embedder "
                f"v{replica.embedder_version} — a reader never mixes "
                f"versions; start with the matching model (or wait for the "
                f"writer's cutover checkpoint to land)")
        # Read-only registry view: the reader fences its detector/cascade
        # versions against the manifest exactly like the embedder above,
        # and the replica's tail parks on registry fences from here on.
        from opencv_facerecognizer_tpu.runtime.registry import ModelRegistry

        replica.registry = ModelRegistry(args.state_dir, metrics=metrics,
                                         readonly=True)
        _registry_fence(replica.registry, args, "reader")
    elif args.state_dir:
        # Writer role: exactly one enrollment owner per state dir. The
        # fcntl lease is taken BEFORE the lifecycle touches anything — a
        # split-brain second writer must fail closed with zero side
        # effects on the live writer's WAL/checkpoints.
        from opencv_facerecognizer_tpu.runtime.replication import (
            WriterLease, WriterLeaseHeldError,
        )

        lease = WriterLease(args.state_dir, metrics=metrics)
        try:
            lease.acquire()
        except WriterLeaseHeldError as exc:
            raise SystemExit(f"ocvf-recognize: {exc}")
        state = StateLifecycle(
            args.state_dir, metrics=metrics,
            keep_checkpoints=args.keep_checkpoints,
            checkpoint_wal_rows=args.checkpoint_wal_rows,
            checkpoint_every_s=args.checkpoint_every_s,
            tracer=tracer,
        )
        # Startup recovery: newest verified checkpoint + WAL replay
        # supersede the fresh --gallery enrollment (the baseline rows are
        # part of the state dir's own first checkpoint, taken below).
        report = state.recover(pipeline.gallery, names)
        print(f"state recovery: {report}", file=sys.stderr)
        recovered_version = int(report.get("embedder_version", 1))
        if args.embedder_version and recovered_version != args.embedder_version:
            # Version fence at the front door: serving a v-N model over
            # v-M rows is exactly the mixed-score corruption the rollout
            # subsystem exists to prevent. (A PENDING cutover to the
            # declared version is completed inside recover() and lands
            # here as a match.)
            raise SystemExit(
                f"ocvf-recognize: --embedder-version {args.embedder_version}"
                f" declared but recovery landed on embedder "
                f"v{recovered_version} — refusing to serve mixed spaces. "
                f"Roll the new embedder out via the staged re-embed "
                f"(runtime.rollout: stage + parity gate + cutover), or "
                f"start the matching model")
        # Model registry (ISSUE 18): recovery attaches one on the fly
        # when the dir already carries a manifest (and completes or
        # abandons any fenced-but-uninstalled swap); a fresh dir gets
        # its manifest created here. The embedder slot mirrors the
        # recovered gallery version, then the same fail-closed startup
        # fence as --embedder-version runs for the other roles.
        from opencv_facerecognizer_tpu.runtime.registry import ModelRegistry

        if state.registry is None:
            state.attach_registry(ModelRegistry(args.state_dir,
                                                metrics=metrics))
        state.registry.mirror_embedder(recovered_version)
        _registry_fence(state.registry, args, "writer")
        if report["recovered_checkpoint"] is None and not report["replayed_records"]:
            # First run against this state dir: make the baseline gallery
            # durable NOW, so a crash before the first enrollment still
            # restarts into a serving gallery.
            state.checkpoint_now(wait=True)

    durability = None
    if state is not None:
        # Degraded-durability state machine + disk-pressure watermarks
        # (README "Degraded-durability runbook"): sustained WAL failure
        # or a critical watermark refuses enrollments closed while
        # serving continues; the probe re-arms automatically. Attaches
        # itself to the lifecycle; the service wires its status channel.
        from opencv_facerecognizer_tpu.runtime.resilience import (
            DurabilityMonitor,
        )

        durability = DurabilityMonitor(
            state, metrics=metrics, tracer=tracer,
            probe_interval_s=args.durability_probe_s,
            low_watermark_bytes=int(args.disk_low_watermark * (1 << 20)))
        # Non-critical sinks shed (with exact per-sink counters) while
        # degraded — the disk's last bytes belong to the WAL.
        durability.attach_sinks(journal=journal, span_sink=span_journal,
                                tracer=tracer)

    train_quantizer_if_wanted(pipeline.gallery)

    slo_monitor = None
    if args.slo:
        from opencv_facerecognizer_tpu.runtime.slo import (
            SLOMonitor, default_objectives,
        )

        short_s, long_s = args.slo_windows
        slo_monitor = SLOMonitor(
            metrics,
            default_objectives(
                drop_counters=RecognizerService.LEDGER_DROP_COUNTERS,
                state=state,
                e2e_p99_s=args.slo_e2e_p99_ms / 1e3,
                queue_wait_p99_s=args.slo_queue_wait_p99_ms / 1e3,
                completion_target=args.slo_completion_target,
                durability_rows=args.slo_durability_rows,
                short_s=short_s, long_s=long_s,
            ),
            tracer=tracer,
            interval_s=args.slo_interval_s,
        )
        if durability is not None and durability.low_watermark_bytes:
            # Disk-pressure SLO: burn = watermark/free (warn at the
            # watermark, critical at 1/6 of it — the same point the
            # monitor pre-empts the degraded flip). Reads the monitor's
            # cached statvfs sample, so /health and the watermark
            # actions see one probe.
            from opencv_facerecognizer_tpu.runtime.slo import (
                disk_free_objective,
            )

            slo_monitor.add_objective(disk_free_objective(
                durability.free_bytes, durability.low_watermark_bytes,
                short_s=short_s, long_s=long_s))

    if args.source == "jsonl":
        connector = JSONLConnector(sys.stdin, sys.stdout, metrics=metrics)
    elif args.source == "socket":
        connector = SocketConnector(host=args.host, port=args.port,
                                    listen=True, metrics=metrics)
    else:
        connector = FakeConnector()

    service = build_service(
        args, pipeline, names, connector, metrics,
        admission=admission, brownout=brownout, journal=journal, state=state,
        tracer=tracer, slo_monitor=slo_monitor, replica=replica)
    # Registry wiring: published results + the tracker key on the full
    # stamp; a reader's re-anchor onto a post-swap manifest flushes the
    # identity caches (the writer-side flush rides the swap coordinator).
    if state is not None and state.registry is not None:
        service.registry = state.registry
    elif replica is not None and replica.registry is not None:
        service.registry = replica.registry
        replica.on_registry_change = service.flush_model_caches
    if slo_monitor is not None and replica is not None:
        # Stale-replica brownout: the lag gauge objective rides the same
        # health verdict the brownout controller already consumes at
        # critical, so a replica that falls behind sheds bulk serving
        # load until its tail catches up.
        from opencv_facerecognizer_tpu.runtime.slo import (
            replication_lag_objective,
        )

        short_s, long_s = args.slo_windows
        slo_monitor.add_objective(replication_lag_objective(
            replica, rows_bound=args.replication_lag_rows,
            short_s=short_s, long_s=long_s))
    if slo_monitor is not None and args.slo_loop_stale_s > 0:
        # Registered after construction: the gauge closes over the
        # service, which is built WITH the monitor (runtime.slo
        # loop_liveness_objective docstring).
        from opencv_facerecognizer_tpu.runtime.slo import (
            loop_liveness_objective,
        )

        short_s, long_s = args.slo_windows
        slo_monitor.add_objective(loop_liveness_objective(
            service, stale_s=args.slo_loop_stale_s,
            short_s=short_s, long_s=long_s))
    supervisor = (ServiceSupervisor(service, state=state)
                  if args.supervised else None)
    expo = None
    if args.expo_port is not None:
        from opencv_facerecognizer_tpu.runtime.expo import ExpoServer

        expo = ExpoServer(service, tracer=tracer, metrics=metrics,
                          port=args.expo_port)
        expo.start()
        print(f"expo endpoint: http://{expo.host}:{expo.port}/",
              file=sys.stderr)
    if supervisor is not None:
        supervisor.start()
    else:
        service.start()

    # Graceful SIGTERM (README "State durability"): drain in-flight
    # batches, final checkpoint, WAL truncate, exit 0 — a deploy-level
    # stop must not cost acknowledged enrollments or in-flight frames.
    import signal
    import threading

    term_event = threading.Event()
    try:
        signal.signal(signal.SIGTERM,
                      lambda signum, frame: term_event.set())
    except ValueError:
        pass  # not the main thread (tests drive main() from a worker)

    profiling = False
    if args.profile_dir:
        import jax

        # Post-warmup so the trace shows steady-state device work, not the
        # one-off XLA compiles (SURVEY.md §5.1; read with TensorBoard's
        # profile plugin or xprof pointed at the directory).
        jax.profiler.start_trace(args.profile_dir)
        profiling = True

    def _stop_profile_if_due() -> None:
        nonlocal profiling
        if profiling and metrics.counter("batches_dispatched") >= args.profile_batches:
            import jax

            jax.profiler.stop_trace()
            profiling = False
            print(f"profile trace written to {args.profile_dir}", file=sys.stderr)

    interrupted = False
    source_finished = False
    unsettled_at_deadline = 0
    try:
        if args.source == "dir":
            import json

            from opencv_facerecognizer_tpu.ops import image as image_ops
            from opencv_facerecognizer_tpu.utils.dataset import _imread_gray

            files = sorted(
                f for f in os.listdir(args.dir)
                if f.lower().endswith((".png", ".jpg", ".jpeg", ".pgm", ".bmp"))
            )
            for fn in files:
                img = _imread_gray(os.path.join(args.dir, fn))
                if img is None:
                    continue
                img = np.asarray(image_ops.resize(img, tuple(args.frame_size)))
                connector.inject(FRAME_TOPIC, {**encode_frame(img), "meta": {"file": fn}})
            # Wait until every admitted frame has SETTLED (published or
            # counted as a drop) — not for a result per file, which an
            # abandoned batch would never deliver.
            deadline = time.monotonic() + 60
            while (service.frames_in_system() > 0
                   and time.monotonic() < deadline
                   and not term_event.is_set()):
                _stop_profile_if_due()
                time.sleep(0.05)
            unsettled_at_deadline = int(service.frames_in_system())
            for message in connector.messages(RESULT_TOPIC):
                print(json.dumps(message))
            source_finished = not term_event.is_set()
        else:
            # Serve until the input stream/socket ends (stdin EOF terminates
            # the process instead of spinning forever), SIGTERM, or Ctrl-C;
            # then let every frame already accepted finish and publish
            # before the teardown in `finally` discards the queues.
            while not connector.eof.wait(timeout=0.5):
                _stop_profile_if_due()
                if term_event.is_set():
                    print("SIGTERM: draining before shutdown", file=sys.stderr)
                    break
            else:
                source_finished = True
            service.drain()
    except KeyboardInterrupt:
        interrupted = True
    finally:
        if profiling:
            import jax

            jax.profiler.stop_trace()
        # ONE shutdown sequence — the exported helper the recovery chaos
        # scenario validates (drain -> stop -> final checkpoint -> WAL
        # truncate), not a hand-rolled copy that could drift from it.
        # Ctrl-C keeps its prompt-teardown semantics via a zero drain
        # budget; EOF/SIGTERM paths already drained above, so the
        # helper's drain is a fast no-op there.
        from opencv_facerecognizer_tpu.runtime.state_store import (
            graceful_shutdown,
        )

        if expo is not None:
            expo.stop()
        shutdown = graceful_shutdown(service, state=state,
                                     supervisor=supervisor,
                                     drain_timeout=0.0 if interrupted else 30.0)
        if shutdown.get("flight_dump"):
            print(f"flight-recorder dump: {shutdown['flight_dump']}",
                  file=sys.stderr)
        if state is not None:
            print(f"final checkpoint: "
                  f"{'written' if shutdown['final_checkpoint'] else 'FAILED (previous kept)'}",
                  file=sys.stderr)
        summary = metrics.summary()
        if summary:
            print(f"metrics: {summary}", file=sys.stderr)
        if shutdown["ledger"]["admitted"]:
            print(f"admission ledger: {shutdown['ledger']}", file=sys.stderr)
        if journal is not None:
            journal.close()
        if span_journal is not None:
            span_journal.close()
        if lease is not None:
            # Last: the final checkpoint/WAL truncate above ran under the
            # lease; releasing it hands enrollment ownership to the next
            # writer with the state dir already quiesced.
            lease.release()
        if metrics_sink:
            metrics_sink.close()
    if source_finished:
        # A finite source (--source dir, or EOF on jsonl/socket) ran to
        # its end: every admitted frame must have completed. Anything
        # else — an abandoned or dead-lettered batch, a shed frame, a
        # frame still in the system when the wait expired — is a failed
        # run, named by its counter, not a quiet exit 0.
        problem = _incomplete_run(shutdown["ledger"], metrics)
        if unsettled_at_deadline and not problem:
            problem = (f"{unsettled_at_deadline} frames were still in the "
                       f"system when the 60 s wait for the directory "
                       f"replay expired (their results were not printed)")
        if problem:
            print(f"ocvf-recognize: FAILED: {problem}", file=sys.stderr)
            return 1
    return 0


def _incomplete_run(ledger, metrics):
    """Why a finished finite source did not complete every admitted
    frame, or None when it did (``RecognizerService.ledger()`` shape)."""
    drops = ledger["drops_by_reason"]
    if not drops and not ledger["in_system"]:
        return None
    from opencv_facerecognizer_tpu.utils import metric_names as mn

    counters = metrics.counters()
    batches = {name: int(counters[name])
               for name in (mn.BATCHES_FAILED, mn.BATCHES_DEAD_LETTERED)
               if counters.get(name)}
    return (f"{int(sum(drops.values()) + ledger['in_system'])} of "
            f"{int(ledger['admitted'])} admitted frames did not complete: "
            f"drops_by_reason={ {k: int(v) for k, v in drops.items()} }, "
            f"in_system={int(ledger['in_system'])}, {batches}")


if __name__ == "__main__":
    sys.exit(main())

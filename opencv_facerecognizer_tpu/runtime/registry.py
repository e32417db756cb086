"""Versioned model registry: every serving-model swap rides the rollout
fence (ISSUE 18; ROADMAP item 1).

PR 11 made EMBEDDER evolution crash-safe, but the detector and the
cascade stage-1 gate were promoted to their measured-fast configs by
editing constructor defaults — no version fence, no parity window, no
atomic cutover, no rollback. Cascade-style detectors are exactly the
models that get retrained and re-tuned in production (PAPERS.md
1508.01292, 1803.10103), so an unfenced detector swap is the most likely
way the fleet silently changes behavior. This module generalizes
``runtime.rollout`` from "embedder version" to a registry of every
served model role:

- **ModelRegistry** — a durable, checksummed manifest
  (``state_dir/registry.json``, atomic tmp+rename+dirsync with an
  embedded sha256 over the canonical manifest bytes) naming the served
  ``(role, version, config, params_path, params_sha256)`` for each of
  ``MODEL_ROLES``. Versions are monotonic per role (a rollback is a NEW
  version whose params equal a prior one's — numbers are never reused,
  so every WAL fence stays unambiguous). The embedder's entry mirrors
  the gallery's ``embedder_version`` (the gallery stays that role's
  source of truth; ``StateLifecycle.perform_cutover`` keeps the mirror
  current).
- **WAL fence + atomic cutover** — a detector/cascade swap goes through
  ``StateLifecycle.perform_registry_cutover``: under the enroll lock,
  candidate params already durable, a strict-fsync ``registry_cutover``
  WAL fence record lands (write-ahead, stamped with the full post-swap
  registry), then the manifest installs atomically and the in-memory
  params publish in one epoch-fenced step (model params are jit
  ARGUMENTS in ``parallel.pipeline`` — a same-architecture swap needs
  ZERO recompiles). No re-embed: gallery rows are untouched, which is
  why these swaps are cheap enough to gate purely on live parity.
- **DetectionParity** — the detector-role parity window: old and new
  detector run side by side on live sampled frames (off the publish
  path, scored on demand); agreement = box-overlap VERDICT match (both
  say face / both say no-face, and when both fire the best boxes
  overlap at IoU >= ``iou_threshold``). Same sliding-window contract as
  ``rollout.DualScoreParity`` (threshold + min samples; no data is not
  a breach), exported as ``registry_parity_*`` gauges with
  ``runtime.slo.registry_parity_objective`` feeding /health.
- **FaceGate retrain rides the swap** — ``evaluate_gate`` scores
  stage-1 recall against THE DETECTOR'S OWN verdicts, so a detector
  swap invalidates the gate's operating point. ``RegistrySwapCoordinator``
  runs ``gate_retrain_fn`` (trained against the CANDIDATE detector's
  verdicts) before the fence, and the (detector, gate) pair cuts over
  atomically — the fleet never serves a new detector under an old
  gate's operating point.
- **Recovery completes or cleanly abandons** — a ``registry_cutover``
  fence past the recovered checkpoint with the manifest still at the
  old version is the crash window between fence and manifest install.
  When the staged candidate params verify (sha256), recovery COMPLETES
  the swap (manifest -> to_version, counted
  ``registry_swaps_completed_recovery``); damaged/missing params
  ABANDON it cleanly (a ``registry_abort`` tombstone marks the fence
  dead, the role stays at from_version, counted loudly) — in every
  interleaving the fleet serves exactly one fenced version per role,
  never a mix.
- **Caches key on the full registry stamp** — the PR 17 tracker stamps
  cache entries with the registry stamp (any role's cutover changes it
  -> lazy flush), and the swap coordinator flushes eagerly
  (``flush_fn``) so no cached identity or cascade verdict from the old
  model outlives its cutover. The jit compile caches are keyed by
  SHAPE with params as call arguments, so a same-architecture swap
  keeps them warm — the bench's zero-recompile-watchdog-trips
  invariant.
- **Auto-rollback with a flight dump** — after cutover the parity
  window keeps scoring (phase ``watch``); a regression below the gate
  inside the watch window rolls back automatically at the next
  monotonic version, forcing a flight-recorder dump
  (``registry_auto_rollback``) with the full swap status attached.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from opencv_facerecognizer_tpu.runtime.rollout import RolloutGateError
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.serialization import (
    atomic_write_bytes,
)
from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC

__all__ = [
    "DetectionParity",
    "MODEL_ROLES",
    "ModelRegistry",
    "RegistryStateError",
    "RegistrySwapCoordinator",
    "box_iou",
    "registry_params_path",
]

logger = logging.getLogger(__name__)

#: every model role the registry fences. The embedder entry mirrors the
#: gallery's ``embedder_version`` (PR 11's machinery stays that role's
#: swap path — it needs the staged re-embed); detector and cascade swap
#: through ``RegistrySwapCoordinator`` (no re-embed needed).
MODEL_ROLES = ("embedder", "detector", "cascade")

#: manifest filename inside ``state_dir``.
MANIFEST_NAME = "registry.json"

#: state-dir subdirectory holding staged candidate params.
PARAMS_DIR = "registry"

#: registry swap phase gauge codes (``registry_phase`` on /prom).
PHASE_CODES = {"idle": 0, "parity": 1, "ready": 2, "cutover": 3,
               "watch": 4, "done": 5, "rolled_back": 6}


class RegistryStateError(RuntimeError):
    """Durable registry state (the manifest or staged candidate params)
    is torn, unreadable, or inconsistent where correctness requires it.
    Fails CLOSED: serving an unfenced or ambiguous model version is the
    outcome this subsystem exists to prevent."""


def registry_params_path(state_dir: str, role: str, version: int) -> str:
    """The conventional durable location for a candidate's params blob:
    ``state_dir/registry/<role>-v<version>.params`` (msgpack for the real
    models — ``FaceGate.save``, ``CNNFaceDetector.save`` and ``SCRFDDetector.save``
    write here)."""
    return os.path.join(str(state_dir), PARAMS_DIR,
                        f"{role}-v{int(version)}.params")


def _file_sha256(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 20), b""):
            digest.update(block)
    return digest.hexdigest()


def _canonical(roles: Dict[str, Any]) -> bytes:
    return json.dumps(roles, sort_keys=True).encode("utf-8")


class ModelRegistry:
    """The durable, checksummed manifest of served model versions.

    File shape (``state_dir/registry.json``)::

        {"format_version": 1,
         "roles": {"embedder": {"version": 1, "config": {...},
                                "params_path": null, "params_sha256": null},
                   "detector": {...}, "cascade": {...}},
         "updated_ts": ..., "checksum": sha256(canonical roles json)}

    Written atomically (tmp + fsync + rename + dirsync); the embedded
    checksum makes a torn or bit-flipped manifest DETECTABLE — the
    offline verifier reports it rc 3 (unreadable) / rc 2 (corrupt), and
    a writer refuses to start over one rather than guess versions.
    ``readonly=True`` (read replicas, the verifier) never writes."""

    def __init__(self, state_dir: str, metrics=None, readonly: bool = False):
        self.state_dir = str(state_dir)
        self.path = os.path.join(self.state_dir, MANIFEST_NAME)
        self.metrics = metrics
        self.readonly = bool(readonly)
        self._lock = threading.Lock()
        self._roles: Dict[str, Dict[str, Any]] = {
            role: {"version": 1, "config": None, "params_path": None,
                   "params_sha256": None}
            for role in MODEL_ROLES
        }
        if os.path.exists(self.path):
            self._roles = self.read_manifest(self.path)["roles"]
        elif not self.readonly:
            os.makedirs(self.state_dir, exist_ok=True)
            self._save_locked()
        self._publish_gauges()

    # ---- durable manifest plumbing ----

    @staticmethod
    def read_manifest(path: str) -> Dict[str, Any]:
        """Parse + validate one manifest file. Raises
        ``RegistryStateError`` with ``.reason`` = ``"unreadable"`` (the
        read/parse itself failed — proves nothing about intent, rc 3 in
        the verifier) or ``"corrupt"`` (checksum/shape mismatch — the
        bytes are damaged, rc 2)."""
        try:
            with open(path, "r", encoding="utf-8") as fh:
                doc = json.loads(fh.read())
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as exc:
            err = RegistryStateError(
                f"registry manifest {path} unreadable: {exc!r}")
            err.reason = "unreadable"
            raise err from exc
        try:
            roles = doc["roles"]
            checksum = doc["checksum"]
            if not isinstance(roles, dict):
                raise TypeError("roles is not an object")
        except (KeyError, TypeError) as exc:
            err = RegistryStateError(
                f"registry manifest {path} malformed: {exc!r}")
            err.reason = "corrupt"
            raise err from exc
        if hashlib.sha256(_canonical(roles)).hexdigest() != checksum:
            err = RegistryStateError(
                f"registry manifest {path} checksum mismatch (torn or "
                f"bit-flipped write)")
            err.reason = "corrupt"
            raise err
        out: Dict[str, Dict[str, Any]] = {}
        for role in MODEL_ROLES:
            entry = roles.get(role)
            if not isinstance(entry, dict) or "version" not in entry:
                err = RegistryStateError(
                    f"registry manifest {path} missing role {role!r}")
                err.reason = "corrupt"
                raise err
            out[role] = {
                "version": int(entry["version"]),
                "config": entry.get("config"),
                "params_path": entry.get("params_path"),
                "params_sha256": entry.get("params_sha256"),
            }
            if "retired" in entry:
                out[role]["retired"] = int(entry["retired"])
        return {"roles": out, "doc": doc}

    def _save_locked(self) -> None:
        if self.readonly:
            raise RegistryStateError(
                "read-only ModelRegistry cannot write the manifest")
        doc = {
            "format_version": 1,
            "roles": self._roles,
            "updated_ts": time.time(),
            "checksum": hashlib.sha256(_canonical(self._roles)).hexdigest(),
        }
        atomic_write_bytes(self.path,
                           json.dumps(doc, sort_keys=True).encode("utf-8"))

    def reload(self) -> None:
        """Re-read the manifest from disk (read replicas re-anchor their
        registry view through this after a fence)."""
        roles = self.read_manifest(self.path)["roles"]
        with self._lock:
            self._roles = roles
        self._publish_gauges()

    def _publish_gauges(self) -> None:
        if self.metrics is None:
            return
        for role, entry in self._roles.items():
            self.metrics.set_gauge(mn.MODEL_VERSION_PREFIX + role,
                                   int(entry["version"]))

    # ---- reads ----

    def version(self, role: str) -> int:
        with self._lock:
            return int(self._roles[role]["version"])

    def describe(self, role: str) -> Dict[str, Any]:
        with self._lock:
            return dict(self._roles[role])

    def stamp(self) -> Dict[str, int]:
        """``{role: version}`` for every role — the full registry stamp
        checkpoint headers, WAL rows, published results and the tracker's
        cache entries carry."""
        with self._lock:
            return {role: int(entry["version"])
                    for role, entry in self._roles.items()}

    def stamp_key(self) -> Tuple[Tuple[str, int], ...]:
        """Hashable form of ``stamp()`` (cache keys compare by opaque
        equality)."""
        return tuple(sorted(self.stamp().items()))

    def status(self) -> Dict[str, Any]:
        """JSON-able snapshot for ``GET /registry``."""
        with self._lock:
            return {"manifest": self.path,
                    "roles": {r: dict(e) for r, e in self._roles.items()}}

    # ---- writes ----

    def install(self, role: str, version: int, config: Any = None,
                params_path: Optional[str] = None,
                params_sha256: Optional[str] = None) -> None:
        """Durably advance one role to ``version`` (atomic manifest
        rewrite). Monotonic per role: versions never move backward or
        repeat — a rollback is a NEW version (the WAL fence stays
        unambiguous)."""
        with self._lock:
            entry = self._roles[role]
            floor = max(int(entry["version"]),
                        int(entry.get("retired", 0)))
            if int(version) <= floor:
                raise ValueError(
                    f"registry versions are monotonic: {role} is at "
                    f"v{entry['version']} (retired through "
                    f"v{entry.get('retired', 0)}), refusing install of "
                    f"v{version} (a rollback is a NEW version whose "
                    f"params equal a prior one's; abandoned numbers are "
                    f"never reused)")
            new_entry = {
                "version": int(version), "config": config,
                "params_path": params_path, "params_sha256": params_sha256,
            }
            if "retired" in entry:
                new_entry["retired"] = int(entry["retired"])
            self._roles[role] = new_entry
            self._save_locked()
        self._publish_gauges()

    def retire(self, role: str, version: int) -> None:
        """Mark ``version`` as burned for ``role`` WITHOUT serving it —
        the recovery path for an ABANDONED fenced swap. The served
        version stays put; future installs must exceed the retired
        number, so a WAL fence sequence never becomes ambiguous."""
        with self._lock:
            entry = self._roles[role]
            if int(version) <= int(entry.get("retired", 0)):
                return
            entry["retired"] = int(version)
            if not self.readonly:
                self._save_locked()

    def mirror_embedder(self, version: int) -> None:
        """Keep the embedder entry in step with the gallery's version
        (the gallery is that role's source of truth; PR 11's cutover
        calls this after the epoch-fenced install). Idempotent; never
        moves backward."""
        with self._lock:
            if int(version) <= int(self._roles["embedder"]["version"]):
                return
            self._roles["embedder"]["version"] = int(version)
            if not self.readonly:
                self._save_locked()
        self._publish_gauges()


def box_iou(a, b) -> float:
    """IoU of two yxyx (or xyxy — symmetric) pixel boxes."""
    ay0, ax0, ay1, ax1 = (float(v) for v in a)
    by0, bx0, by1, bx1 = (float(v) for v in b)
    iy0, ix0 = max(ay0, by0), max(ax0, bx0)
    iy1, ix1 = min(ay1, by1), min(ax1, bx1)
    inter = max(0.0, iy1 - iy0) * max(0.0, ix1 - ix0)
    if inter <= 0.0:
        return 0.0
    area_a = max(0.0, ay1 - ay0) * max(0.0, ax1 - ax0)
    area_b = max(0.0, by1 - by0) * max(0.0, bx1 - bx0)
    union = area_a + area_b - inter
    return inter / union if union > 0.0 else 0.0


class DetectionParity:
    """Old-vs-new DETECTOR agreement over a sliding window of live
    frames: the registry's parity definition for the detector role
    (module docstring). One sample per frame; agreement = verdict match
    (both fire / both pass) AND, when both fire, the best box pair
    overlaps at IoU >= ``iou_threshold``. Pure host math — it runs on
    demand off the publish path, never the hot loop. The window/sample
    contract mirrors ``rollout.DualScoreParity`` exactly (the SLO gauge
    reads ``disagreement``; below the sample floor no data is not a
    breach)."""

    def __init__(self, old_detect_fn: Callable[[np.ndarray], List],
                 new_detect_fn: Callable[[np.ndarray], List],
                 threshold: float = 0.98, min_samples: int = 16,
                 window: int = 256, iou_threshold: float = 0.5,
                 metrics=None):
        self.old_detect_fn = old_detect_fn
        self.new_detect_fn = new_detect_fn
        self.threshold = float(threshold)
        self.min_samples = int(min_samples)
        self.iou_threshold = float(iou_threshold)
        self.metrics = metrics
        self._agreements: deque = deque(maxlen=int(window))
        self._lock = threading.Lock()

    @staticmethod
    def _boxes(verdict) -> List:
        """Normalize a detect fn's output to a list of boxes: accepts a
        plain box list, or the ``detect_batch``-shaped ``(boxes, scores,
        valid)`` triple for one frame."""
        if verdict is None:
            return []
        if isinstance(verdict, tuple) and len(verdict) == 3:
            boxes, _scores, valid = verdict
            boxes = np.asarray(boxes)
            valid = np.asarray(valid, bool)
            return [boxes[i] for i in range(boxes.shape[0]) if valid[i]]
        return list(verdict)

    def _frame_agreement(self, old_boxes: List, new_boxes: List) -> float:
        if bool(old_boxes) != bool(new_boxes):
            return 0.0  # verdict mismatch: one fired, the other passed
        if not old_boxes:
            return 1.0  # both say no-face
        best = max(box_iou(a, b) for a in old_boxes for b in new_boxes)
        return 1.0 if best >= self.iou_threshold else 0.0

    def score(self, frames, old_boxes_list: Optional[List[List]] = None
              ) -> int:
        """Score frames through both detectors (or reuse the serving
        detector's live verdicts via ``old_boxes_list`` — the publish
        path already paid for them); returns samples recorded."""
        recorded = 0
        for i, frame in enumerate(frames):
            frame = np.asarray(frame)
            if old_boxes_list is not None:
                old_boxes = list(old_boxes_list[i])
            else:
                old_boxes = self._boxes(self.old_detect_fn(frame))
            new_boxes = self._boxes(self.new_detect_fn(frame))
            value = self._frame_agreement(old_boxes, new_boxes)
            with self._lock:
                self._agreements.append(value)
            recorded += 1
        if self.metrics is not None:
            with self._lock:
                n = len(self._agreements)
                agreement = (sum(self._agreements) / n) if n else 0.0
            self.metrics.set_gauge(mn.REGISTRY_PARITY_SAMPLES, n)
            self.metrics.set_gauge(mn.REGISTRY_PARITY_AGREEMENT,
                                   round(agreement, 4))
        return recorded

    def reset(self) -> None:
        """Clear the window (the post-cutover watch must not inherit the
        pre-cutover samples — a regression has to show on NEW traffic)."""
        with self._lock:
            self._agreements.clear()

    @property
    def samples(self) -> int:
        with self._lock:
            return len(self._agreements)

    @property
    def agreement(self) -> float:
        with self._lock:
            if not self._agreements:
                return 0.0
            return sum(self._agreements) / len(self._agreements)

    @property
    def disagreement(self) -> float:
        """1 - agreement once the window has data; 0.0 below the sample
        floor (no data is not a breach — the SLO gauge contract)."""
        with self._lock:
            n = len(self._agreements)
            if n < self.min_samples:
                return 0.0
            return 1.0 - sum(self._agreements) / n

    def ok(self) -> bool:
        with self._lock:
            n = len(self._agreements)
            return (n >= self.min_samples
                    and sum(self._agreements) / n >= self.threshold)


class RegistrySwapCoordinator:
    """Drives one detector/cascade registry swap end to end (module
    docstring): the live detection-parity window, the FaceGate retrain
    against the candidate detector, the gated atomic cutover through
    ``StateLifecycle.perform_registry_cutover``, and the post-cutover
    watch with auto-rollback.

    ``old_detect_fn``/``new_detect_fn`` produce per-frame verdicts (box
    lists, or ``detect_batch``-shaped triples) for the parity window —
    both optional, but without them the gate never opens and cutover
    needs ``force=True``. ``install_fn()`` performs the in-memory
    epoch-fenced install (pipeline param publish — it runs INSIDE the
    enroll-locked cutover, so keep it to attribute publishes);
    ``flush_fn(stamp)`` flushes the tracker/cascade caches right after
    the swap; ``gate_retrain_fn()`` returns the retrained stage-1 gate
    artifacts for a detector swap (run BEFORE the fence — the pair cuts
    over atomically). ``rollback_install_fn()`` restores the previous
    params in memory when a watch regression auto-rolls-back."""

    def __init__(self, state, registry: ModelRegistry, role: str,
                 to_version: int, *,
                 old_detect_fn: Optional[Callable] = None,
                 new_detect_fn: Optional[Callable] = None,
                 config: Any = None,
                 params_path: Optional[str] = None,
                 install_fn: Optional[Callable[[], None]] = None,
                 rollback_install_fn: Optional[Callable[[], None]] = None,
                 flush_fn: Optional[Callable[[Dict[str, int]], None]] = None,
                 gate_retrain_fn: Optional[Callable[[], Any]] = None,
                 parity_threshold: float = 0.98,
                 parity_min_samples: int = 16,
                 parity_window: int = 256,
                 parity_iou: float = 0.5,
                 watch_min_samples: int = 16,
                 live_sample_interval_s: float = 0.05,
                 metrics=None, tracer=None):
        if role not in MODEL_ROLES or role == "embedder":
            raise ValueError(
                f"RegistrySwapCoordinator handles detector/cascade swaps; "
                f"role {role!r} is not one (the embedder rolls out through "
                f"runtime.rollout — it needs the staged re-embed)")
        self.state = state
        self.registry = registry
        self.role = str(role)
        self.to_version = int(to_version)
        self.from_version = registry.version(role)
        if self.to_version <= self.from_version:
            raise ValueError(
                f"to_version {to_version} must exceed the served "
                f"{role} version {self.from_version} (versions are "
                f"monotonic; a rollback is a NEW version)")
        self.config = config
        self.params_path = params_path
        self.params_sha256 = (_file_sha256(params_path)
                              if params_path is not None
                              and os.path.exists(params_path) else None)
        self.install_fn = install_fn
        self.rollback_install_fn = rollback_install_fn
        self.flush_fn = flush_fn
        self.gate_retrain_fn = gate_retrain_fn
        self.gate_retrained: Any = None
        self.metrics = metrics
        self.tracer = tracer
        self.watch_min_samples = int(watch_min_samples)
        self.parity = (DetectionParity(old_detect_fn, new_detect_fn,
                                       threshold=parity_threshold,
                                       min_samples=parity_min_samples,
                                       window=parity_window,
                                       iou_threshold=parity_iou,
                                       metrics=metrics)
                       if old_detect_fn is not None
                       and new_detect_fn is not None else None)
        self._phase = "idle"
        self._live_q: deque = deque(maxlen=64)
        self._live_lock = threading.Lock()
        self._live_interval_s = float(live_sample_interval_s)
        self._last_live_t = 0.0
        self.cutover_seq: Optional[int] = None
        self.rollback_seq: Optional[int] = None
        self._set_phase("idle" if self.parity is None else "parity")

    # ---- phase bookkeeping ----

    def _set_phase(self, phase: str) -> None:
        self._phase = phase
        if self.metrics is not None:
            self.metrics.set_gauge(mn.REGISTRY_PHASE, PHASE_CODES[phase])
        if self.tracer is not None:
            self.tracer.emit(self.tracer.new_trace(), "registry_phase",
                             topic=LIFECYCLE_TOPIC, phase=phase,
                             role=self.role, to_version=self.to_version)

    @property
    def phase(self) -> str:
        return self._phase

    # ---- live parity sampling ----

    def offer_live(self, frame: np.ndarray,
                   faces: Optional[List[Dict[str, Any]]] = None) -> None:
        """Publish-path hook (``RecognizerService._publish``): sample the
        frame, rate-limited, COPIED (the frame lives in a recycled
        staging buffer), with the serving detector's verdict boxes when
        the caller has them. Cheap and non-blocking by contract — the
        hot path pays one clock read in the common (not-due) case."""
        if self.parity is None or self._phase in ("done", "rolled_back"):
            return
        now = time.monotonic()
        if now - self._last_live_t < self._live_interval_s:
            return
        self._last_live_t = now
        boxes = None
        if faces is not None:
            boxes = [np.asarray(f["box"], np.float32) for f in faces
                     if "box" in f]
        with self._live_lock:
            self._live_q.append((np.asarray(frame).copy(), boxes))  # ocvf-lint: boundary=host-sync -- the publish path hands us the batch's HOST input frame (staging-ring numpy, never a device array); the copy exists precisely because that buffer is recycled

    def drain_live(self) -> int:
        """Score every queued live sample (the swap driver's thread, or
        tests calling it synchronously); returns samples scored. After
        cutover this feeds the WATCH window and a regression triggers
        the auto-rollback."""
        with self._live_lock:
            samples = list(self._live_q)
            self._live_q.clear()
        scored = 0
        for frame, boxes in samples:
            scored += self.score_parity(
                [frame], old_boxes_list=None if boxes is None else [boxes])
        return scored

    def score_parity(self, frames,
                     old_boxes_list: Optional[List[List]] = None) -> int:
        """Score frames through both detectors (tests and the chaos
        harness call this directly with synthetic traffic). In phase
        ``watch`` a completed window below the gate auto-rolls-back."""
        if self.parity is None:
            return 0
        n = self.parity.score(frames, old_boxes_list=old_boxes_list)
        if (self._phase == "parity" and self.parity.ok()):
            self._set_phase("ready")
        elif self._phase == "watch":
            self.check_watch()
        return n

    def parity_ok(self) -> bool:
        return self.parity is not None and self.parity.ok()

    # ---- the gated atomic cutover ----

    def cutover(self, force: bool = False) -> int:
        """Gate -> FaceGate retrain (detector swaps) -> WAL fence ->
        manifest install + epoch-fenced in-memory publish -> cache flush
        -> forced checkpoint -> watch. Returns the fence record's WAL
        sequence. Raises ``RolloutGateError`` (the same refusal type the
        embedder rollout gates with) when the parity window has not
        cleared its threshold (``force`` overrides — and is required
        when no parity detectors were wired)."""
        if not force:
            reasons = []
            if self.parity is None:
                reasons.append("no parity window wired (old/new detect fns)")
            elif not self.parity.ok():
                reasons.append(
                    f"parity gate not met: agreement "
                    f"{self.parity.agreement:.4f} over "
                    f"{self.parity.samples} samples (need >= "
                    f"{self.parity.threshold:g} over >= "
                    f"{self.parity.min_samples})")
            if reasons:
                if self.metrics is not None:
                    self.metrics.incr(mn.REGISTRY_SWAPS_BLOCKED)
                raise RolloutGateError(
                    f"{self.role} swap refused: " + "; ".join(reasons))
        if self.gate_retrain_fn is not None and self.gate_retrained is None:
            # The stage-1 gate's operating point is defined AGAINST the
            # detector's verdicts — retrain it against the CANDIDATE
            # before the fence so the pair cuts over atomically.
            self.gate_retrained = self.gate_retrain_fn()
            if self.metrics is not None:
                self.metrics.incr(mn.REGISTRY_GATE_RETRAINS)
        self._set_phase("cutover")
        seq = self.state.perform_registry_cutover(
            self.role, self.to_version, config=self.config,
            params_path=self.params_path,
            params_sha256=self.params_sha256,
            install_fn=self.install_fn)
        self.cutover_seq = seq
        if self.flush_fn is not None:
            # Eager cache flush: no cached identity or cascade verdict
            # computed under the OLD model outlives its cutover (the
            # tracker's stamp keying catches stragglers lazily).
            self.flush_fn(self.registry.stamp())
        # Forced checkpoint: the swap is fence-durable already (a crash
        # here recovers INTO the new version from the manifest/fence);
        # the checkpoint stamps the new registry and lets replicas
        # re-anchor past the fence.
        if not self.state.checkpoint_now(wait=True):
            self.state.maybe_checkpoint(force=True)
            logger.warning(
                "post-swap checkpoint did not land; the forced-checkpoint "
                "latch will retry (recovery completes the swap meanwhile)")
        if self.parity is not None:
            self.parity.reset()
            self._set_phase("watch")
        else:
            self._set_phase("done")
        return seq

    # ---- the post-cutover watch + auto-rollback ----

    def check_watch(self) -> bool:
        """Evaluate the post-cutover parity window; True when the swap
        regressed and was auto-rolled-back. A completed watch window at
        or above the gate settles the swap (phase ``done``)."""
        if self._phase != "watch" or self.parity is None:
            return False
        n = self.parity.samples
        if n < self.watch_min_samples:
            return False
        if self.parity.agreement >= self.parity.threshold:
            self._set_phase("done")
            return False
        self.auto_rollback()
        return True

    def auto_rollback(self) -> int:
        """Parity regressed inside the watch window: roll the role back
        at the NEXT monotonic version (numbers never reuse — the fence
        stays unambiguous), restore the previous params in memory, and
        force a flight-recorder dump with the full swap status — the
        forensic artifact the chaos scenario parses."""
        status = self.status()
        if self.metrics is not None:
            self.metrics.incr(mn.REGISTRY_AUTO_ROLLBACKS)
        if self.tracer is not None:
            self.tracer.dump("registry_auto_rollback",
                             extra={"registry_swap": status}, force=True)
        logger.warning(
            "registry %s swap v%d -> v%d auto-rolling-back: watch parity "
            "%.4f over %d samples below gate %.4g", self.role,
            self.from_version, self.to_version,
            self.parity.agreement if self.parity is not None else 0.0,
            self.parity.samples if self.parity is not None else 0,
            self.parity.threshold if self.parity is not None else 0.0)
        seq = self.state.perform_registry_cutover(
            self.role, self.to_version + 1, config=None,
            params_path=None, params_sha256=None,
            install_fn=self.rollback_install_fn)
        self.rollback_seq = seq
        if self.flush_fn is not None:
            self.flush_fn(self.registry.stamp())
        if not self.state.checkpoint_now(wait=True):
            self.state.maybe_checkpoint(force=True)
        self._set_phase("rolled_back")
        return seq

    def rollback(self) -> int:
        """Operator-driven rollback: the same mechanism as the automatic
        one, at the next monotonic version."""
        return self.auto_rollback()

    # ---- observability ----

    def status(self) -> Dict[str, Any]:
        """JSON-able snapshot for ``GET /registry`` and the chaos
        report."""
        out = {
            "role": self.role,
            "phase": self._phase,
            "from_version": self.from_version,
            "to_version": self.to_version,
            "cutover_seq": self.cutover_seq,
            "rollback_seq": self.rollback_seq,
            "gate_retrained": self.gate_retrained is not None,
            "params_path": self.params_path,
            "parity": None,
        }
        if self.parity is not None:
            out["parity"] = {
                "samples": self.parity.samples,
                "agreement": round(self.parity.agreement, 4),
                "threshold": self.parity.threshold,
                "min_samples": self.parity.min_samples,
                "iou_threshold": self.parity.iou_threshold,
                "ok": self.parity.ok(),
            }
        return out

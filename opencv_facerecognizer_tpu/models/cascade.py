"""Stage-1 face-proposal network: the compact front of the detection
cascade (ISSUE 13; design anchors PAPERS.md — *Compact Convolutional
Neural Network Cascade for Face Detection* (1508.01292) and *A Fast Face
Detection Method via CNN* (1803.10103)).

The pre-PR-1 stage table (source deleted in PR 21; not re-measured on the
local chip) had detect dominating device cost at every dispatch bucket
(b128: 0.716 ms detect vs 0.449/0.561/0.454 ms for crop/embed/match), yet
most real camera frames carry zero faces. The cascade answer: run a tiny
proposal net at REDUCED resolution over every frame first, and invoke the
full detector only on frames it scores face-possible. This module is that
first stage:

- ``CascadeNet`` average-pools the input down by ``downsample`` (256x256
  -> 64x64 at the default 4), then a two-block stride-4 conv stack emits
  a coarse TILE logit map — one logit per ``downsample * 4``-pixel tile,
  so the decision is tileable (a per-tile consumer can gate regions; the
  serving runtime gates whole frames on the max tile).
- ``frame_scores`` reduces the tile map to one face-possible probability
  per frame: ``sigmoid(max(tile logits))`` — a frame is worth the full
  detector iff ANY tile might hold a face. Recall-shaped by construction:
  one confident tile keeps the frame.
- Training is per-tile weighted BCE against box-derived tile targets
  (a tile is positive when a face center lands in it, dilated by one tile
  so boundary-straddling faces never train as pure negatives), with
  ``pos_weight`` biasing toward recall — a stage-1 false negative is a
  face the system never sees, while a false positive merely wastes one
  full-detector slot.

The serving integration (``RecognitionPipeline.cascade_scores`` +
``RecognizerService``) compacts surviving frames into the bucketed
dispatch ladder and settles rejected frames as ``completed_empty``; see
runtime/recognizer.py. ``evaluate_gate`` measures the operating point the
bench gate enforces: recall vs the full detector's own verdicts.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

#: pixels per tile logit at ``downsample=d``: each conv block halves the
#: pooled map twice, so one logit covers ``d * TILE_CONV_STRIDE`` pixels.
TILE_CONV_STRIDE = 4

#: The default operating point (``FaceGate.threshold`` and the serving
#: ``--cascade-threshold`` default): chosen recall-first — the bench gate
#: requires >= 0.99 of stage-2-detectable faces to survive stage 1 here,
#: and the per-tile pos_weight training pushes face tiles far above it.
DEFAULT_THRESHOLD = 0.3


class CascadeNet(nn.Module):
    """Tiny stride-``downsample * 4`` FCN: avg-pool downsample -> two
    conv blocks -> per-tile face logit map ``[N, Ht, Wt]``.

    Sized to be orders cheaper than ``DetectorNet``: the pool shrinks the
    spatial extent ``downsample**2``-fold before the first conv, and the
    widest layer is ``features[-1]`` channels at 1/(4*downsample) of the
    input resolution — the whole forward is a rounding error next to one
    full-detector pass, which is what makes rejecting a face-free frame
    here a near-free early exit.
    """

    features: Sequence[int] = (8, 16)
    downsample: int = 4
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.dtype) / 255.0
        d = int(self.downsample)
        if d > 1:
            x = nn.avg_pool(x, (d, d), strides=(d, d))
        for feats in self.features:
            x = nn.Conv(feats, (3, 3), strides=(2, 2), use_bias=False,
                        dtype=self.dtype)(x)
            x = nn.GroupNorm(num_groups=min(4, int(feats)),
                             dtype=self.dtype)(x)
            x = nn.relu(x)
        # Negative bias init: an untrained gate scores everything
        # face-unlikely instead of passing noise through at ~0.5 — the
        # fail-closed-toward-stage-2 direction is set by TRAINING, not
        # by the init (see pos_weight in train_face_gate).
        logits = nn.Conv(1, (1, 1), dtype=jnp.float32,
                         bias_init=nn.initializers.constant(-2.0))(x)
        return logits[..., 0]  # [N, Ht, Wt] tile logits


def frame_scores(net: CascadeNet, params: Dict[str, Any],
                 frames: jnp.ndarray) -> jnp.ndarray:
    """[N, H, W] frames -> [N] face-possible probabilities: the max tile
    logit through a sigmoid. Pure and jit-friendly — the serving pipeline
    compiles exactly this per dispatch rung."""
    logits = net.apply({"params": params}, frames)
    return jax.nn.sigmoid(jnp.max(logits, axis=(1, 2)))


def tile_targets(boxes: np.ndarray, num_boxes: np.ndarray,
                 image_size: Tuple[int, int], tile_px: int) -> np.ndarray:
    """Host-side per-tile targets from padded pixel yxyx boxes: a tile is
    positive when a face-box center lands in it, dilated by one tile in
    every direction (a face straddling a tile boundary must not teach its
    neighbors 'no face here'). Returns ``[N, Ht, Wt]`` float32 0/1."""
    n = boxes.shape[0]
    ht = max(1, image_size[0] // tile_px)
    wt = max(1, image_size[1] // tile_px)
    targets = np.zeros((n, ht, wt), dtype=np.float32)
    for i in range(n):
        for b in range(int(num_boxes[i])):
            y0, x0, y1, x1 = boxes[i, b]
            ty = int(np.clip((y0 + y1) / 2 / tile_px, 0, ht - 1))
            tx = int(np.clip((x0 + x1) / 2 / tile_px, 0, wt - 1))
            targets[i, max(0, ty - 1):ty + 2, max(0, tx - 1):tx + 2] = 1.0
    return targets


def gate_loss(logits: jnp.ndarray, targets: jnp.ndarray,
              pos_weight: float = 2.0) -> jnp.ndarray:
    """Per-tile weighted BCE. ``pos_weight`` > 1 buys recall: a missed
    face tile costs ``pos_weight`` x a passed background tile, so the
    trained operating curve puts face frames far above any reasonable
    threshold before background frames start leaking through."""
    p = jnp.clip(jax.nn.sigmoid(logits), 1e-6, 1.0 - 1e-6)
    bce = -(pos_weight * targets * jnp.log(p)
            + (1.0 - targets) * jnp.log(1.0 - p))
    return jnp.mean(bce)


def train_face_gate(net: CascadeNet, images: np.ndarray, boxes: np.ndarray,
                    num_boxes: np.ndarray, *, steps: int = 400,
                    batch_size: int = 32, learning_rate: float = 3e-3,
                    pos_weight: float = 2.0, seed: int = 0,
                    params: Optional[Dict] = None,
                    log_every: int = 0) -> Dict[str, Any]:
    """Train on (images [N,H,W] in [0,255], padded boxes, counts): the
    same scene format ``train_detector`` consumes, so one synthetic-scene
    set trains both cascade stages."""
    h, w = images.shape[1], images.shape[2]
    tile_px = int(net.downsample) * TILE_CONV_STRIDE
    targets = tile_targets(boxes, num_boxes, (h, w), tile_px)
    if params is None:
        params = net.init(jax.random.PRNGKey(seed),
                          jnp.zeros((1, h, w)))["params"]
    optimizer = optax.adam(learning_rate)
    opt_state = optimizer.init(params)

    @jax.jit  # ocvf-lint: boundary=jit-recompile-hazard -- offline training step, one fixed batch shape per train() call; never reached from the serving loop
    def step(params, opt_state, x, t):
        def loss_fn(p):
            return gate_loss(net.apply({"params": p}, x), t, pos_weight)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    n = images.shape[0]
    batch_size = min(batch_size, n)
    rng = np.random.default_rng(seed)
    x_all = jnp.asarray(images, jnp.float32)
    t_all = jnp.asarray(targets)
    for i in range(steps):
        idx = jnp.asarray(rng.choice(n, size=batch_size, replace=n < batch_size))
        params, opt_state, loss = step(params, opt_state, x_all[idx], t_all[idx])
        if log_every and (i + 1) % log_every == 0:
            print(f"  gate step {i + 1}/{steps}: loss {float(loss):.4f}")  # ocvf-lint: boundary=host-sync -- offline training progress log; nothing here runs on the serving loop
    return params


class FaceGate:
    """Stage-1 wrapper with the ``CNNFaceDetector``-shaped lifecycle:
    ``train`` / ``score_batch`` / ``save`` / ``load``. Holds the
    operating ``threshold`` the serving runtime defaults to (overridable
    per service via ``--cascade-threshold``)."""

    def __init__(self, features: Sequence[int] = (8, 16),
                 downsample: int = 4,
                 threshold: float = DEFAULT_THRESHOLD):
        self.net = CascadeNet(features=tuple(features),
                              downsample=int(downsample))
        self.threshold = float(threshold)
        self._params: Optional[Dict] = None

        def _score(params, frames):
            return frame_scores(self.net, params, frames)

        self._score_jit = jax.jit(_score)  # ocvf-lint: boundary=jit-recompile-hazard -- built ONCE at construction for the offline score_batch convenience path; serving compiles through RecognitionPipeline.cascade_scores' cache-keyed builder instead

    @property
    def params(self):
        return self._params

    def load_params(self, params) -> None:
        self._params = params

    @property
    def tile_px(self) -> int:
        return int(self.net.downsample) * TILE_CONV_STRIDE

    def train(self, images, boxes, num_boxes, **kwargs) -> "FaceGate":
        self._params = train_face_gate(self.net, images, boxes, num_boxes,
                                       params=self._params, **kwargs)
        return self

    def score_batch(self, frames) -> jnp.ndarray:
        """[N, H, W] -> [N] face-possible probabilities (device array;
        callers materialize). Offline/eval convenience — serving goes
        through ``RecognitionPipeline.cascade_scores`` for the per-rung
        compile cache."""
        if self._params is None:
            raise RuntimeError("FaceGate.score_batch before train()/load()")
        return self._score_jit(self._params, jnp.asarray(frames, jnp.float32))

    # -- checkpointing (msgpack, pickle-free, like CNNFaceDetector) --

    def save(self, path: str) -> None:
        import json

        from flax import serialization as flax_serialization

        from opencv_facerecognizer_tpu.utils.serialization import (
            atomic_write_bytes,
        )

        if self._params is None:
            raise RuntimeError("FaceGate.save called before train()/load()")
        payload = {
            "header": {
                "format_version": 1,
                "config_json": json.dumps({
                    "features": list(self.net.features),
                    "downsample": self.net.downsample,
                    "threshold": self.threshold,
                }),
            },
            "params": jax.tree_util.tree_map(np.asarray, self._params),
        }
        atomic_write_bytes(path, flax_serialization.msgpack_serialize(payload))

    @classmethod
    def load(cls, path: str) -> "FaceGate":
        import json

        from flax import serialization as flax_serialization

        with open(path, "rb") as fh:
            payload = flax_serialization.msgpack_restore(fh.read())
        config = json.loads(payload["header"]["config_json"])
        gate = cls(features=tuple(config["features"]),
                   downsample=config["downsample"],
                   threshold=config.get("threshold", DEFAULT_THRESHOLD))
        gate.load_params(jax.tree_util.tree_map(jnp.asarray,
                                                payload["params"]))
        return gate


def evaluate_gate(gate: FaceGate, detector, scenes: np.ndarray,
                  gt_counts: Optional[np.ndarray] = None,
                  threshold: Optional[float] = None,
                  batch_size: int = 32) -> Dict[str, Any]:
    """The cascade's operating-point measurement, AGAINST THE FULL
    DETECTOR'S OWN VERDICTS: stage-1 recall = the fraction of
    stage-2-detectable face frames that stage 1 keeps (a face stage 2
    cannot detect is not a cascade loss — it was never going to be
    served either way), and the face-free reject rate = the early-exit
    win on frames stage 2 would have scanned for nothing. The bench
    gate pins recall >= 0.99 at the default threshold.

    With ``gt_counts`` (per-scene ground-truth face counts), a
    "detectable face frame" requires BOTH a stage-2 detection AND a real
    face: a detector FALSE POSITIVE on a background frame is not a face
    the cascade can lose — the gate rejecting it is a precision win,
    reported separately as ``detector_fp_suppressed``. Without
    ``gt_counts`` every stage-2 firing counts as detectable (the
    conservative, label-free form)."""
    thr = gate.threshold if threshold is None else float(threshold)
    scenes = np.asarray(scenes, np.float32)
    detectable = kept_detectable = facefree = rejected_facefree = 0
    fp_frames = fp_suppressed = 0
    for start in range(0, len(scenes), batch_size):
        chunk = scenes[start:start + batch_size]
        _boxes, _scores, valid = detector.detect_batch(chunk)
        fires = np.asarray(valid).any(axis=1)  # ocvf-lint: boundary=host-sync -- offline evaluation readback; never on the serving loop
        scores = np.asarray(gate.score_batch(chunk))  # ocvf-lint: boundary=host-sync -- offline evaluation readback; never on the serving loop
        keep = scores >= thr
        if gt_counts is not None:
            gt = np.asarray(gt_counts[start:start + batch_size]) > 0
            has_face = fires & gt
            fp = fires & ~gt
            fp_frames += int(fp.sum())
            fp_suppressed += int((fp & ~keep).sum())
        else:
            has_face = fires
        detectable += int(has_face.sum())
        kept_detectable += int((has_face & keep).sum())
        facefree += int((~has_face).sum())
        rejected_facefree += int((~has_face & ~keep).sum())
    out = {
        "threshold": thr,
        "detectable_frames": detectable,
        "stage1_recall": (kept_detectable / detectable
                          if detectable else float("nan")),
        "facefree_frames": facefree,
        "facefree_reject_rate": (rejected_facefree / facefree
                                 if facefree else float("nan")),
    }
    if gt_counts is not None:
        out["detector_fp_frames"] = fp_frames
        out["detector_fp_suppressed"] = fp_suppressed
    return out

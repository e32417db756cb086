"""CNN face detector (Flax): the TPU-native replacement for the reference's
Haar-cascade ``detectMultiScale`` stage (BASELINE.json:5: "the Haar-cascade
detectMultiScale stage becomes a batched ... CNN detector"; design anchors
PAPERS.md:6-7 — CNN-cascade / single-pass CNN detection).

Instead of translating the cascade's image pyramid + sliding window (serial,
shape-dynamic — hostile to XLA), this is a single-stage anchor-free
("center-heatmap") detector:

- A small FCN backbone at stride 8 emits a face-center heatmap plus box
  size and sub-cell offset maps — all dense convs, MXU work.
- Decode is static-shape end-to-end (SURVEY.md §7 "hard parts"): 3x3
  max-pool peak suppression, ``top_k`` K candidates, box assembly, then the
  fixed-K ``ops.nms`` mask. One jitted graph, batchable under vmap — the
  "fixed-size outputs + on-device NMS" contract from SURVEY.md §2.2.
- Training: penalty-reduced focal loss on a Gaussian-splatted heatmap +
  masked L1 on size/offset (the standard center-heatmap recipe), jitted.

``CNNFaceDetector.detect(img)`` keeps the reference's ``CascadedDetector``
API (SURVEY.md §2.1 "Face detector wrapper"): returns a host-side list of
(x0, y0, x1, y1) boxes for one image; the batched device path used by the
serving runtime is ``detect_batch``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from opencv_facerecognizer_tpu.ops import nms as nms_ops

STRIDE = 8


class DetectorNet(nn.Module):
    """Stride-8 FCN: downsampling conv blocks -> heatmap/size/offset heads.

    ``space_to_depth`` folds an s x s pixel block into s^2 input channels
    before the first conv (lossless). Why: the MXU is a 128-lane systolic
    array, and convs with 1-16 input channels at 128x128+ resolution run at
    a small fraction of peak (round-3 stage attribution measured the
    default stem at MFU 0.08 — 55% of the whole fused batch). With s2d=4
    every conv sees >=16 input channels at <=64x64, the net stride stays 8
    (conv blocks downsample 8/s2d), and the per-cell receptive field is
    unchanged in pixels. Decode/train code is stride-8 either way.
    """

    features: Sequence[int] = (16, 32, 64)
    head_features: int = 64
    space_to_depth: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.dtype) / 255.0
        s = int(self.space_to_depth)
        if STRIDE % s:
            # A non-divisor would FLOOR remaining (s=3 -> remaining 2, net
            # stride 6) while decode still scales by STRIDE=8 — every box
            # silently mis-scaled. Refuse instead.
            raise ValueError(
                f"space_to_depth={s} must divide the decode stride {STRIDE}"
            )
        if s > 1:
            n, h, w, c = x.shape
            x = x.reshape(n, h // s, s, w // s, s, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // s, w // s, s * s * c)
        remaining = STRIDE // s  # conv blocks must still reach stride 8
        accum = 1
        for feats in self.features:
            stride = 2 if accum < remaining else 1
            accum *= stride
            x = nn.Conv(feats, (3, 3), strides=(stride, stride),
                        use_bias=False, dtype=self.dtype)(x)
            x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.Conv(feats, (3, 3), use_bias=False, dtype=self.dtype)(x)
            x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
            x = nn.relu(x)
        if accum != remaining:
            raise ValueError(
                f"features={self.features!r} with space_to_depth={s} cannot "
                f"reach stride {STRIDE}: blocks provide x{accum}, need "
                f"x{remaining} (add blocks or lower space_to_depth)"
            )
        h = nn.Conv(self.head_features, (3, 3), dtype=self.dtype)(x)
        h = nn.relu(h)
        heatmap = nn.Conv(1, (1, 1), dtype=jnp.float32,
                          bias_init=nn.initializers.constant(-4.0))(h)
        size = nn.Conv(2, (1, 1), dtype=jnp.float32)(h)
        offset = nn.Conv(2, (1, 1), dtype=jnp.float32)(h)
        return {
            "heatmap": heatmap[..., 0],  # [N, Hs, Ws] logits
            "size": size,  # [N, Hs, Ws, 2] (h, w) in output-cell units
            "offset": offset,  # [N, Hs, Ws, 2] sub-cell (dy, dx)
        }


def decode_detections(
    outputs: Dict[str, jnp.ndarray],
    max_faces: int = 16,
    score_threshold: float = 0.3,
    iou_threshold: float = 0.4,
):
    """Batched static-shape decode: outputs -> (boxes [N,K,4] pixel yxyx,
    scores [N,K], valid [N,K])."""
    heat = jax.nn.sigmoid(outputs["heatmap"])  # [N, Hs, Ws]
    size = outputs["size"]
    offset = outputs["offset"]
    n, hs, ws = heat.shape

    # CenterNet peak NMS: keep cells that are their 3x3 neighborhood max.
    pooled = nn.max_pool(heat[..., None], (3, 3), strides=(1, 1), padding="SAME")[..., 0]
    peaks = jnp.where(heat >= pooled - 1e-6, heat, 0.0)

    flat = peaks.reshape(n, hs * ws)
    k = min(max_faces * 4, hs * ws)  # over-collect, NMS trims
    scores, idx = jax.lax.top_k(flat, k)  # [N, k]
    cy = (idx // ws).astype(jnp.float32)
    cx = (idx % ws).astype(jnp.float32)
    take = lambda m: jnp.take_along_axis(m.reshape(n, hs * ws, 2), idx[..., None], axis=1)
    sz = take(size)
    off = take(offset)
    cy = cy + off[..., 0]
    cx = cx + off[..., 1]
    bh = jnp.maximum(sz[..., 0], 1e-3)
    bw = jnp.maximum(sz[..., 1], 1e-3)
    boxes = jnp.stack(
        [
            (cy - bh / 2) * STRIDE,
            (cx - bw / 2) * STRIDE,
            (cy + bh / 2) * STRIDE,
            (cx + bw / 2) * STRIDE,
        ],
        axis=-1,
    )  # [N, k, 4]

    def per_image(b, s):
        return nms_ops.nms_fixed(b, s, max_faces, iou_threshold, score_threshold)

    boxes, scores, valid = jax.vmap(per_image)(boxes, scores)
    # Clamp to the decoded canvas: cy +/- bh/2 freely projects past the
    # edge for border faces, and every consumer (serving pipeline included
    # — this is the shared decode) expects in-frame pixel boxes. Bounds are
    # EXCLUSIVE yxyx (y1 == H is a legal bottom-edge box, matching dataset
    # targets and crop slicing). Invalid slots are zero boxes, unaffected.
    # detect_batch additionally clips to the caller's pre-padding extent.
    lim = jnp.asarray(
        [hs * STRIDE, ws * STRIDE, hs * STRIDE, ws * STRIDE], boxes.dtype
    )
    boxes = jnp.clip(boxes, 0.0, lim)
    return boxes, scores, valid


def gaussian_heatmap_targets(
    boxes: np.ndarray, num_boxes: np.ndarray, image_size: Tuple[int, int], max_boxes: int
):
    """Host-side target builder: padded pixel yxyx boxes [N, B, 4] + counts
    -> (heatmap [N,Hs,Ws], size [N,Hs,Ws,2], offset [N,Hs,Ws,2],
    mask [N,Hs,Ws]). Gaussian splat radius follows the box size."""
    n = boxes.shape[0]
    hs, ws = image_size[0] // STRIDE, image_size[1] // STRIDE
    heat = np.zeros((n, hs, ws), dtype=np.float32)
    size = np.zeros((n, hs, ws, 2), dtype=np.float32)
    offset = np.zeros((n, hs, ws, 2), dtype=np.float32)
    mask = np.zeros((n, hs, ws), dtype=np.float32)
    ys, xs = np.mgrid[0:hs, 0:ws]
    for i in range(n):
        for b in range(int(num_boxes[i])):
            y0, x0, y1, x1 = boxes[i, b] / STRIDE
            cy, cx = (y0 + y1) / 2, (x0 + x1) / 2
            bh, bw = max(y1 - y0, 1e-3), max(x1 - x0, 1e-3)
            iy, ix = int(np.clip(cy, 0, hs - 1)), int(np.clip(cx, 0, ws - 1))
            sigma = max((bh + bw) / 8.0, 0.7)
            g = np.exp(-((ys - iy) ** 2 + (xs - ix) ** 2) / (2 * sigma**2))
            heat[i] = np.maximum(heat[i], g)
            size[i, iy, ix] = (bh, bw)
            offset[i, iy, ix] = (cy - iy, cx - ix)
            mask[i, iy, ix] = 1.0
    return heat, size, offset, mask


def detector_loss(outputs, targets, alpha: float = 2.0, beta: float = 4.0):
    """Penalty-reduced focal loss on the heatmap + masked L1 on size/offset."""
    pred = jax.nn.sigmoid(outputs["heatmap"])
    pred = jnp.clip(pred, 1e-6, 1.0 - 1e-6)
    gt = targets["heatmap"]
    pos = (gt >= 0.999).astype(jnp.float32)
    pos_loss = -pos * ((1 - pred) ** alpha) * jnp.log(pred)
    neg_loss = -(1 - pos) * ((1 - gt) ** beta) * (pred**alpha) * jnp.log(1 - pred)
    num_pos = jnp.maximum(jnp.sum(pos), 1.0)
    heat_loss = (jnp.sum(pos_loss) + jnp.sum(neg_loss)) / num_pos
    m = targets["mask"][..., None]
    size_loss = jnp.sum(jnp.abs(outputs["size"] - targets["size"]) * m) / num_pos
    off_loss = jnp.sum(jnp.abs(outputs["offset"] - targets["offset"]) * m) / num_pos
    return heat_loss + 0.1 * size_loss + off_loss


def make_detector_train_step(model: DetectorNet, optimizer):
    @jax.jit
    def step(params, opt_state, images, targets):
        def loss_fn(p):
            return detector_loss(model.apply({"params": p}, images), targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def train_detector(
    model: DetectorNet,
    images: np.ndarray,
    boxes: np.ndarray,
    num_boxes: np.ndarray,
    *,
    steps: int = 300,
    batch_size: int = 16,
    learning_rate: float = 1e-3,
    seed: int = 0,
    params: Optional[Dict] = None,
    log_every: int = 0,
):
    """Train on (images [N,H,W], padded boxes [N,B,4], counts [N])."""
    h, w = images.shape[1], images.shape[2]
    heat, size, offset, mask = gaussian_heatmap_targets(
        boxes, num_boxes, (h, w), boxes.shape[1]
    )
    if params is None:
        # jitted: an eager init dispatches (and on an accelerator compiles)
        # every initializer op one by one; the values are bit-identical.
        params = jax.jit(model.init)(
            jax.random.PRNGKey(seed), jnp.zeros((1, h, w)))["params"]
    optimizer = optax.adam(learning_rate)
    opt_state = optimizer.init(params)
    step = make_detector_train_step(model, optimizer)
    n = images.shape[0]
    batch_size = min(batch_size, n)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(images, jnp.float32)
    t_all = {
        "heatmap": jnp.asarray(heat),
        "size": jnp.asarray(size),
        "offset": jnp.asarray(offset),
        "mask": jnp.asarray(mask),
    }
    for i in range(steps):
        idx = jnp.asarray(rng.choice(n, size=batch_size, replace=n < batch_size))
        batch_t = {k: v[idx] for k, v in t_all.items()}
        params, opt_state, loss = step(params, opt_state, x[idx], batch_t)
        if log_every and (i + 1) % log_every == 0:
            print(f"  detector step {i + 1}/{steps}: loss {float(loss):.4f}")
    return params


def evaluate_detector(
    detector: "CNNFaceDetector",
    scenes: np.ndarray,
    gt_boxes: np.ndarray,
    gt_counts: np.ndarray,
    iou_threshold: float = 0.5,
    batch_size: int = 32,
):
    """Detection quality vs oracle boxes: recall/precision@IoU (VERDICT
    round-1 item #4 — the Haar-cascade replacement must be measurably good,
    not merely present).

    Greedy matching per image: predictions in descending score order claim
    the best still-unmatched ground-truth box with IoU >= threshold.
    Returns {"recall", "precision", "f1", "mean_matched_iou",
    "num_gt", "num_pred"}.
    """
    scenes = np.asarray(scenes, np.float32)
    gt_boxes = np.asarray(gt_boxes, np.float32)
    gt_counts = np.asarray(gt_counts)
    tp = fp = 0
    total_gt = int(gt_counts.sum())
    matched_ious = []
    for start in range(0, len(scenes), batch_size):
        chunk = scenes[start : start + batch_size]
        boxes, scores, valid = (np.asarray(v) for v in detector.detect_batch(chunk))
        for i in range(len(chunk)):
            gi = start + i
            gts = gt_boxes[gi, : int(gt_counts[gi])]
            taken = np.zeros(len(gts), dtype=bool)
            order = np.argsort(-scores[i])
            for j in order:
                if not valid[i, j]:
                    continue
                py0, px0, py1, px1 = boxes[i, j]
                best_iou, best_g = 0.0, -1
                for gidx, (gy0, gx0, gy1, gx1) in enumerate(gts):
                    if taken[gidx]:
                        continue
                    iy = max(0.0, min(py1, gy1) - max(py0, gy0))
                    ix = max(0.0, min(px1, gx1) - max(px0, gx0))
                    inter = iy * ix
                    union = ((py1 - py0) * (px1 - px0)
                             + (gy1 - gy0) * (gx1 - gx0) - inter)
                    iou = inter / union if union > 0 else 0.0
                    if iou > best_iou:
                        best_iou, best_g = iou, gidx
                if best_g >= 0 and best_iou >= iou_threshold:
                    taken[best_g] = True
                    tp += 1
                    matched_ious.append(best_iou)
                else:
                    fp += 1
    recall = tp / total_gt if total_gt else float("nan")
    precision = tp / (tp + fp) if (tp + fp) else float("nan")
    f1 = (2 * precision * recall / (precision + recall)
          if precision + recall > 0 else 0.0)
    return {
        "recall": recall,
        "precision": precision,
        "f1": f1,
        "mean_matched_iou": float(np.mean(matched_ious)) if matched_ious else 0.0,
        "num_gt": total_gt,
        "num_pred": tp + fp,
    }


class CNNFaceDetector:
    """``CascadedDetector``-shaped wrapper (SURVEY.md §2.1): ``detect(img)``
    -> list of (x0, y0, x1, y1) int tuples, plus the batched device path."""

    #: Default config selected by measurement (scripts/explore_perf.py,
    #: 2026-07-30, v5e): s2d=4/(64,64) runs the batch-32 forward in 0.199 ms
    #: vs 0.584 ms for the old 1-channel-stem (16,32,64) net — 2.9x — at
    #: equal-or-better detection quality (recall 1.0, precision 1.0,
    #: IoU 0.904 vs 0.901 on the held-out synthetic scenes).
    def __init__(
        self,
        features: Sequence[int] = (64, 64),
        head_features: int = 64,
        max_faces: int = 16,
        score_threshold: float = 0.3,
        iou_threshold: float = 0.4,
        space_to_depth: int = 4,
    ):
        self.net = DetectorNet(features=tuple(features),
                               head_features=head_features,
                               space_to_depth=space_to_depth)
        self.max_faces = int(max_faces)
        self.score_threshold = float(score_threshold)
        self.iou_threshold = float(iou_threshold)
        self._params: Optional[Dict] = None

        def _detect(params, images):
            outputs = self.net.apply({"params": params}, images)
            return decode_detections(
                outputs, self.max_faces, self.score_threshold, self.iou_threshold
            )

        self._detect_jit = jax.jit(_detect)

    def train(self, images, boxes, num_boxes, **kwargs):
        self._params = train_detector(
            self.net, images, boxes, num_boxes, params=self._params, **kwargs
        )
        return self

    def load_params(self, params) -> None:
        self._params = params

    @property
    def params(self):
        return self._params

    # -- checkpointing (msgpack, pickle-free, like utils.serialization) --

    def save(self, path: str) -> None:
        import json

        from flax import serialization as flax_serialization

        if self._params is None:
            raise RuntimeError("CNNFaceDetector.save called before train()/load_params()")
        payload = {
            "header": {
                "format_version": 1,
                "config_json": json.dumps({
                    "features": list(self.net.features),
                    "head_features": self.net.head_features,
                    "max_faces": self.max_faces,
                    "score_threshold": self.score_threshold,
                    "iou_threshold": self.iou_threshold,
                    "space_to_depth": self.net.space_to_depth,
                }),
            },
            "params": jax.tree_util.tree_map(np.asarray, self._params),
        }
        from opencv_facerecognizer_tpu.utils.serialization import atomic_write_bytes

        atomic_write_bytes(path, flax_serialization.msgpack_serialize(payload))

    @classmethod
    def load(cls, path: str) -> "CNNFaceDetector":
        import json

        from flax import serialization as flax_serialization

        with open(path, "rb") as fh:
            payload = flax_serialization.msgpack_restore(fh.read())
        config = json.loads(payload["header"]["config_json"])
        det = cls(
            features=tuple(config["features"]),
            head_features=config["head_features"],
            max_faces=config["max_faces"],
            score_threshold=config["score_threshold"],
            iou_threshold=config["iou_threshold"],
            space_to_depth=config.get("space_to_depth", 1),  # pre-r3 ckpts
        )
        det.load_params(jax.tree_util.tree_map(jnp.asarray, payload["params"]))
        return det

    def detect_batch(self, images: jnp.ndarray):
        """[N, H, W] -> (boxes [N,K,4] yxyx, scores [N,K], valid [N,K]) on device.

        Arbitrary H/W are accepted (the CascadedDetector-shaped contract):
        inputs are edge-padded up to the next multiple of the decode stride
        (which every space_to_depth setting divides), and box coordinates
        are unaffected since padding grows only the bottom/right."""
        if self._params is None:
            raise RuntimeError("CNNFaceDetector.detect called before train()/load_params()")
        images = jnp.asarray(images, jnp.float32)
        h, w = images.shape[1], images.shape[2]
        ph, pw = (-h) % STRIDE, (-w) % STRIDE
        if ph or pw:
            images = jnp.pad(images, ((0, 0), (0, ph), (0, pw)), mode="edge")
        boxes, scores, valid = self._detect_jit(self._params, images)
        # Decode clamps to its (possibly padded) canvas; additionally clip
        # to the CALLER's pre-padding extent so border faces never report
        # coordinates inside the padding strip. Bounds are exclusive yxyx
        # (y1 == h is a legal bottom-edge box).
        lim = jnp.asarray([h, w, h, w], boxes.dtype)
        boxes = jnp.clip(boxes, 0.0, lim)
        return boxes, scores, valid

    def detect(self, img: np.ndarray):
        """Single grayscale image -> [(x0, y0, x1, y1)] like the reference's
        CascadedDetector.detect (x/y order flipped to its x-first tuples)."""
        boxes, scores, valid = self.detect_batch(jnp.asarray(img, jnp.float32)[None])
        boxes = np.asarray(boxes[0])
        valid = np.asarray(valid[0])
        out = []
        for b, ok in zip(boxes, valid):
            if ok:
                y0, x0, y1, x1 = (int(round(float(v))) for v in b)
                out.append((x0, y0, x1, y1))
        return out

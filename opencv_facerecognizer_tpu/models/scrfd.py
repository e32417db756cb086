"""SCRFD face detector (Flax): the detector the field ships in front of an
ArcFace embedder (Guo et al., "Sample and Computation Redistribution for
Efficient Face Detection", arXiv:2105.04714; insightface
``detection/scrfd/configs/scrfd/scrfd_10g.py``; the model zoo's
``buffalo_l`` pairs its keypoint variant ``det_10g`` with ``w600k_r50``),
served through the same fused step as ``CNNFaceDetector`` behind the
``parallel.pipeline.Detector`` boundary.

The equations, for x of [N, H, W, 3] with H and W multiples of 32 (the
defaults are SCRFD-10GF: 9.91 G multiply-adds at 640x480 by
``multiply_adds``, published 9.98 GFLOPs; 3.86 M parameters):

- backbone ``ResNetV1e`` of ``BasicBlock``s, BatchNorm + ReLU. Deep stem:
  conv3x3 s2 (3 -> 28), conv3x3 (28 -> 28), conv3x3 (28 -> 56), each
  BN-ReLU, then max-pool 3x3 s2: stride 4. Four stages of (3, 4, 2, 3)
  blocks and (56, 88, 88, 224) planes; block(x) =
  ReLU(BN(conv3x3(ReLU(BN(conv3x3_s(x))))) + sc(x)); the first block of
  stages 2-4 has s = 2 and sc = BN(conv1x1(avgpool2x2_s2(x))), elsewhere sc
  is the identity. C3, C4, C5 leave at strides 8, 16, 32.
- neck ``PAFPN`` (56 channels, no norm, no activation, every conv biased):
  1x1 laterals; top-down L_i += up2_nearest(L_{i+1}); 3x3 ``fpn`` convs;
  bottom-up N_{i+1} = P_{i+1} + conv3x3_s2(N_i); 3x3 ``pafpn`` convs on the
  two coarser levels.
- head ``SCRFDHead``: a tower of 3 x [conv3x3 (56 -> 80, 80 -> 80, 80 -> 80),
  GroupNorm(16), ReLU] that classification and regression share, one set of
  weights over the three strides (``strides_share``); cls = conv3x3(80 -> A),
  reg = Scale_l * conv3x3(80 -> 4A), A = 2 anchors a position (sizes 16, 32 /
  64, 128 / 256, 512), 12,600 anchors at 640x480.
- score = sigmoid(cls); a box is the anchor's centre (x * stride, y * stride)
  minus / plus the four distances (left, top, right, bottom) * stride.
  Inference: score over 0.5, greedy NMS at IoU 0.4, the ``max_faces`` best.

Design, TPU-first, and what differs from the published code:

- Static shapes end to end, as ``models.detector``: ``decode`` takes the
  ``pre_nms`` (4 x ``max_faces`` unless told) best-scored anchors of all
  levels, runs the fixed-K ``ops.nms`` over them and returns exactly
  ``max_faces`` slots with a ``valid`` mask. The published inference keeps
  every anchor over the threshold.
- BatchNorm as ``models.iresnet``'s: the STORED moments live in ``params``;
  training normalises by the batch's own moments (the module's ``calibrate``
  flag) and a calibration pass stores them once training ends, where the
  published code keeps running averages.
- Convolutions take bf16 operands with f32 parameters; BatchNorm, GroupNorm
  and the head's two outputs are f32. Layout NHWC. A [N, H, W] grayscale
  batch is replicated onto the stem's 3 planes, scaled (x - 127.5) / 128.
- Training (``train_scrfd``): ATSS assignment (9 nearest anchors a level,
  IoU over mean + deviation, centre inside the face), quality focal loss
  (beta 2) and DIoU loss (weight 2, weighted by the score) as published;
  Adam with warm-up and cosine decay where the published schedule is SGD
  over 640 epochs, no augmentation, no keypoint branch.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from opencv_facerecognizer_tpu.models.iresnet import (
    _BatchNorm, _conv, parameter_count)
from opencv_facerecognizer_tpu.ops import nms as nms_ops

#: the header's ``kind`` of a checkpoint this module writes
KIND = "scrfd"
#: where C3, C4, C5 leave the backbone: fixed by the architecture
STRIDES = (8, 16, 32)
#: anchor side = base x scale, ``ANCHOR_SCALES`` a position
ANCHOR_BASES = (16, 64, 256)
ANCHOR_SCALES = (1, 2)
#: the frame the published cost is stated at (VGA), (height, width)
VGA = (480, 640)


class _GroupNorm(nn.Module):
    """GroupNorm over (H, W, channels of a group), f32, eps as torch's."""

    groups: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        n, h, w, c = x.shape
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        g = x.astype(jnp.float32).reshape(n, h, w, self.groups, c // self.groups)
        mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
        var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
        g = (g - mean) * jax.lax.rsqrt(var + self.eps)
        return (g.reshape(n, h, w, c) * scale + bias).astype(self.dtype)


def _biased_conv(features: int, kernel: int, stride: int, dtype, name: str):
    """The neck's convolutions: pad 1 for 3x3, pad 0 for 1x1, with bias."""
    pad = (kernel - 1) // 2
    return nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                   padding=((pad, pad), (pad, pad)), dtype=dtype, name=name)


class _BasicBlock(nn.Module):
    features: int
    stride: int = 1
    eps: float = 1e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def bn(name):
            return _BatchNorm(self.eps, self.calibrate, self.dtype, name=name)

        y = _conv(self.features, 3, self.stride, self.dtype, "conv1")(x)
        y = nn.relu(bn("bn1")(y))
        y = bn("bn2")(_conv(self.features, 3, 1, self.dtype, "conv2")(y))
        if self.stride != 1 or x.shape[-1] != self.features:
            if self.stride != 1:
                x = nn.avg_pool(x, (self.stride,) * 2, strides=(self.stride,) * 2)
            x = bn("shortcut_bn")(
                _conv(self.features, 1, 1, self.dtype, "shortcut_conv")(x))
        return nn.relu(y + x)


class SCRFDNet(nn.Module):
    """[N, H, W] or [N, H, W, in_channels] pixels in [0, 255] ->
    {"cls": three [N, Hs, Ws, A] logit maps, "reg": three [N, Hs, Ws, A, 4]
    (left, top, right, bottom) distance maps in units of the level's
    stride}, strides 8, 16, 32. The defaults are the published 10GF; tests
    use a small variant (any H, W divisible by 32)."""

    stem_features: Sequence[int] = (28, 28, 56)
    stage_features: Sequence[int] = (56, 88, 88, 224)
    stage_blocks: Sequence[int] = (3, 4, 2, 3)
    neck_features: int = 56
    head_features: int = 80
    head_convs: int = 3
    head_groups: int = 16
    num_anchors: int = 2
    in_channels: int = 3
    strides_share: bool = True
    eps: float = 1e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def bn(name):
            return _BatchNorm(self.eps, self.calibrate, self.dtype, name=name)

        if x.ndim == 3:
            x = jnp.broadcast_to(x[..., None], (*x.shape, self.in_channels))
        x = ((x.astype(jnp.float32) - 127.5) / 128.0).astype(self.dtype)
        for i, feats in enumerate(self.stem_features):
            x = _conv(feats, 3, 2 if i == 0 else 1, self.dtype, f"stem_conv{i}")(x)
            x = nn.relu(bn(f"stem_bn{i}")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        taps = []
        for s, (feats, blocks) in enumerate(zip(self.stage_features,
                                                self.stage_blocks)):
            for b in range(blocks):
                x = _BasicBlock(feats, 2 if b == 0 and s > 0 else 1, self.eps,
                                self.calibrate, self.dtype,
                                name=f"stage{s + 1}_block{b}")(x)
            taps.append(x)
        levels = self._neck(taps[1:])
        a = self.num_anchors
        heads = {}  # one set of modules, called on every level, when shared

        def head(lv):
            tag = "" if self.strides_share else f"{lv}_"
            if tag not in heads:
                heads[tag] = (
                    [(_conv(self.head_features, 3, 1, self.dtype, f"head_{tag}conv{i}"),
                      _GroupNorm(self.head_groups, self.eps, self.dtype,
                                 name=f"head_{tag}gn{i}"))
                     for i in range(self.head_convs)],
                    nn.Conv(a, (3, 3), padding=((1, 1), (1, 1)), dtype=jnp.float32,
                            bias_init=nn.initializers.constant(-4.6),
                            name=f"head_{tag}cls"),
                    nn.Conv(4 * a, (3, 3), padding=((1, 1), (1, 1)),
                            dtype=jnp.float32,
                            bias_init=nn.initializers.constant(1.0),
                            name=f"head_{tag}reg"))
            return heads[tag]

        cls, reg = [], []
        for lv, feat in enumerate(levels):
            tower, to_cls, to_reg = head(lv)
            for conv, norm in tower:
                feat = nn.relu(norm(conv(feat)))
            dist = to_reg(feat)
            scale = self.param(f"head_scale{lv}", nn.initializers.ones, (),
                               jnp.float32)
            cls.append(to_cls(feat))
            reg.append((scale * dist).reshape((*dist.shape[:3], a, 4)))
        return {"cls": tuple(cls), "reg": tuple(reg)}

    def _neck(self, taps):
        c, dt = self.neck_features, self.dtype
        lat = [_biased_conv(c, 1, 1, dt, f"neck_lateral{i}")(t)
               for i, t in enumerate(taps)]
        for i in range(len(lat) - 1, 0, -1):
            up = jnp.repeat(jnp.repeat(lat[i], 2, axis=1), 2, axis=2)
            lat[i - 1] = lat[i - 1] + up
        outs = [_biased_conv(c, 3, 1, dt, f"neck_fpn{i}")(v)
                for i, v in enumerate(lat)]
        for i in range(len(outs) - 1):
            outs[i + 1] = outs[i + 1] + _biased_conv(
                c, 3, 2, dt, f"neck_down{i}")(outs[i])
        return [outs[0]] + [_biased_conv(c, 3, 1, dt, f"neck_pafpn{i}")(v)
                            for i, v in enumerate(outs[1:])]


# ---- anchors, decode ----


def anchor_grid(frame_size: Tuple[int, int], num_anchors: int = 2):
    """(centres [A, 2] (y, x) pixels, stride of each anchor [A], side of
    each anchor [A]) in the order the net's maps flatten: level, row,
    column, anchor."""
    centres, strides, sides = [], [], []
    for stride, base in zip(STRIDES, ANCHOR_BASES):
        hs, ws = frame_size[0] // stride, frame_size[1] // stride
        yy, xx = np.mgrid[0:hs, 0:ws].astype(np.float32) * stride
        c = np.stack([yy, xx], axis=-1).reshape(-1, 1, 2)
        centres.append(np.broadcast_to(c, (hs * ws, num_anchors, 2)).reshape(-1, 2))
        strides.append(np.full((hs * ws * num_anchors,), stride, np.float32))
        sides.append(np.tile(np.asarray(ANCHOR_SCALES[:num_anchors], np.float32)
                             * base, hs * ws))
    return (np.concatenate(centres), np.concatenate(strides),
            np.concatenate(sides))


def flatten_outputs(outputs) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The three levels' maps as (logits [N, A], distances [N, A, 4] in
    stride units), anchors in ``anchor_grid``'s order."""
    n = outputs["cls"][0].shape[0]
    return (jnp.concatenate([c.reshape(n, -1) for c in outputs["cls"]], axis=1),
            jnp.concatenate([r.reshape(n, -1, 4) for r in outputs["reg"]], axis=1))


def distances_to_boxes(centres, strides, dist):
    """Anchor centres [..., 2] (y, x), strides [...] and (left, top, right,
    bottom) distances [..., 4] in stride units -> pixel yxyx boxes."""
    d = dist * strides[..., None]
    return jnp.stack([centres[..., 0] - d[..., 1], centres[..., 1] - d[..., 0],
                      centres[..., 0] + d[..., 3], centres[..., 1] + d[..., 2]],
                     axis=-1)


def decode(outputs, frame_size: Tuple[int, int], max_faces: int,
           score_threshold: float = 0.5, iou_threshold: float = 0.4,
           pre_nms: Optional[int] = None):
    """Batched static-shape decode: the net's maps -> (boxes [N, K, 4] pixel
    yxyx, scores [N, K], valid [N, K]), K = ``max_faces``, best first: the
    ``pre_nms`` best-scored anchors of all levels, their boxes, greedy NMS
    over those above the threshold, clamp to the frame."""
    logits, dist = flatten_outputs(outputs)
    a = logits.shape[1]
    centres, strides, _sides = anchor_grid(frame_size, outputs["cls"][0].shape[-1])
    k = min(int(pre_nms or 4 * max_faces), a)
    scores, idx = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    boxes = distances_to_boxes(
        jnp.take(jnp.asarray(centres), idx, axis=0),
        jnp.take(jnp.asarray(strides), idx, axis=0),
        jnp.take_along_axis(dist, idx[..., None], axis=1))

    def per_image(b, s):
        return nms_ops.nms_fixed(b, s, max_faces, iou_threshold, score_threshold)

    boxes, scores, valid = jax.vmap(per_image)(boxes, scores)
    h, w = frame_size
    boxes = jnp.clip(boxes, 0.0, jnp.asarray([h, w, h, w], boxes.dtype))
    return boxes, scores, valid


# ---- cost ----


def multiply_adds(net: SCRFDNet, frame_size: Tuple[int, int] = VGA) -> int:
    """Multiply-adds of one frame through every convolution (9,914,793,600
    for the 10GF at 640x480, published as 9.98 GFLOPs; norms, pools and adds
    left out)."""
    h, w = frame_size[0] // 2, frame_size[1] // 2
    ch, total = net.in_channels, 0
    for feats in net.stem_features:
        total += 9 * ch * feats * h * w
        ch = feats
    h, w = h // 2, w // 2
    extents = []
    for s, (feats, blocks) in enumerate(zip(net.stage_features, net.stage_blocks)):
        if s > 0:
            h, w = h // 2, w // 2
        total += 9 * ch * feats * h * w + 9 * feats * feats * h * w
        if s > 0 or ch != feats:
            total += ch * feats * h * w
        total += (blocks - 1) * 2 * 9 * feats * feats * h * w
        ch = feats
        extents.append((feats, h * w))
    c = net.neck_features
    cells = [hw for _f, hw in extents[1:]]
    total += sum(f * c * hw for f, hw in extents[1:])      # laterals
    total += 9 * c * c * sum(cells)                        # fpn convs
    total += 2 * 9 * c * c * sum(cells[1:])                # bottom-up and pafpn convs
    f, a = net.head_features, net.num_anchors
    tower = 9 * c * f + (net.head_convs - 1) * 9 * f * f
    return total + (tower + 9 * f * 5 * a) * sum(cells)


# ---- parameters ----


def random_params(net: SCRFDNet, frame_size: Tuple[int, int],
                  seed: int = 0) -> Dict[str, Any]:
    """Seeded parameters with nothing left at a default a fault could hide
    behind: convolutions as flax draws them, norm scales and stored
    variances in [0.5, 1.5], norm biases and stored means N(0, 0.1), the
    head's biases N(0, 1), the levels' scales in [0.5, 1.5]."""
    key = jax.random.PRNGKey(int(seed))
    # jitted: an eager init dispatches every initializer one by one
    params = jax.jit(net.init)(key, jnp.zeros((1, *frame_size), jnp.float32))["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    spread = lambda k, s: jax.random.uniform(k, s, minval=0.5, maxval=1.5)  # noqa: E731
    small = lambda k, s: 0.1 * jax.random.normal(k, s)  # noqa: E731
    draws = {"scale": spread, "var": spread, "mean": small}
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        names = [getattr(p, "key", "") for p in path]
        draw = draws.get(names[-1])
        if names[-1] == "bias":
            draw = jax.random.normal if names[-2].endswith(("cls", "reg")) else small
        if names[-1].startswith("head_scale"):
            draw = spread
        leaves.append(leaf if draw is None else
                      draw(jax.random.fold_in(key, i + 1), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _with_stats(params, sown):
    """``params`` with the moments sown by a ``calibrate`` pass stored."""
    out = dict(params)
    for name, sub in sown.items():
        out[name] = sub[0] if name in ("mean", "var") else _with_stats(params[name], sub)
    return out


def calibrate_batch_stats(net: SCRFDNet, params, frames) -> Dict[str, Any]:
    """``params`` with every BatchNorm's stored mean and variance replaced
    by the moments of its input over ``frames``: one forward pass in which
    every BatchNorm normalises by its own batch."""
    probe = net.clone(calibrate=True)
    # ocvf-lint: boundary=jit-recompile-hazard -- one-off calibration pass when training ends, never on the serving path
    _out, sown = jax.jit(lambda p, v: probe.apply(
        {"params": p}, v, mutable=["batch_stats"]))(params, frames)
    return _with_stats(params, sown["batch_stats"])


# ---- training ----


def _iou_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[A, 4], [G, 4] yxyx -> [A, G]."""
    y0 = np.maximum(a[:, None, 0], b[None, :, 0])
    x0 = np.maximum(a[:, None, 1], b[None, :, 1])
    y1 = np.minimum(a[:, None, 2], b[None, :, 2])
    x1 = np.minimum(a[:, None, 3], b[None, :, 3])
    inter = np.maximum(y1 - y0, 0) * np.maximum(x1 - x0, 0)
    area = lambda r: (r[:, 2] - r[:, 0]) * (r[:, 3] - r[:, 1])  # noqa: E731
    return inter / np.maximum(area(a)[:, None] + area(b)[None, :] - inter, 1e-9)


def atss_assign(frame_size: Tuple[int, int], boxes: np.ndarray, count: int,
                num_anchors: int = 2, topk: int = 9) -> np.ndarray:
    """ATSS (Zhang et al., arXiv:1912.02424) of one frame's ``count``
    ground-truth yxyx boxes to the anchors: [A] index of the face each
    anchor is positive for, -1 for none. Candidates are the ``topk``
    anchors a level whose centres lie nearest the face's; a candidate is
    positive when its IoU with the face reaches the candidates' mean plus
    deviation and its centre lies inside the face; an anchor positive for
    two faces goes to the one it overlaps more."""
    centres, strides, sides = anchor_grid(frame_size, num_anchors)
    assigned = np.full((len(centres),), -1, np.int64)
    if count == 0:
        return assigned
    gt = np.asarray(boxes[:count], np.float32)
    half = sides[:, None] / 2
    anchors = np.concatenate([centres - half, centres + half], axis=1)
    iou = _iou_np(anchors, gt)
    gt_c = np.stack([(gt[:, 0] + gt[:, 2]) / 2, (gt[:, 1] + gt[:, 3]) / 2], axis=1)
    dist = np.linalg.norm(centres[:, None, :] - gt_c[None], axis=-1)
    candidate = np.zeros(iou.shape, bool)
    for stride in STRIDES:
        level = np.flatnonzero(strides == stride)
        near = np.argsort(dist[level], axis=0, kind="stable")[:topk]
        for g in range(count):
            candidate[level[near[:, g]], g] = True
    for g in range(count):
        c = iou[candidate[:, g], g]
        inside = ((centres[:, 0] > gt[g, 0]) & (centres[:, 0] < gt[g, 2])
                  & (centres[:, 1] > gt[g, 1]) & (centres[:, 1] < gt[g, 3]))
        candidate[:, g] &= (iou[:, g] >= c.mean() + c.std()) & inside
    best = np.where(candidate, iou, -1.0)
    hit = best.max(axis=1) >= 0
    assigned[hit] = best.argmax(axis=1)[hit]
    return assigned


def scrfd_targets(frame_size: Tuple[int, int], boxes: np.ndarray,
                  num_boxes: np.ndarray, num_anchors: int = 2):
    """Host-side targets of a set of frames: (positive [N, A] bool, the
    assigned face's box [N, A, 4], zeros where not positive)."""
    n = len(boxes)
    a = len(anchor_grid(frame_size, num_anchors)[0])
    pos = np.zeros((n, a), bool)
    target = np.zeros((n, a, 4), np.float32)
    for i in range(n):
        assigned = atss_assign(frame_size, boxes[i], int(num_boxes[i]), num_anchors)
        pos[i] = assigned >= 0
        target[i, pos[i]] = boxes[i][assigned[pos[i]]]
    return pos, target


def _iou_and_diou(pred, target):
    """Elementwise over [..., 4] yxyx boxes: (IoU, DIoU loss)."""
    area = lambda r: (jnp.maximum(r[..., 2] - r[..., 0], 0.0)  # noqa: E731
                      * jnp.maximum(r[..., 3] - r[..., 1], 0.0))
    lo = jnp.maximum(pred[..., :2], target[..., :2])
    hi = jnp.minimum(pred[..., 2:], target[..., 2:])
    inter = jnp.prod(jnp.maximum(hi - lo, 0.0), axis=-1)
    iou = inter / jnp.maximum(area(pred) + area(target) - inter, 1e-6)
    outer = (jnp.maximum(pred[..., 2:], target[..., 2:])
             - jnp.minimum(pred[..., :2], target[..., :2]))
    gap = (pred[..., :2] + pred[..., 2:] - target[..., :2] - target[..., 2:]) / 2
    penalty = jnp.sum(gap * gap, axis=-1) / jnp.maximum(
        jnp.sum(outer * outer, axis=-1), 1e-6)
    return iou, 1.0 - iou + penalty


def scrfd_loss(outputs, pos, target, frame_size: Tuple[int, int],
               beta: float = 2.0, box_weight: float = 2.0):
    """Quality focal loss on every anchor (the target of a positive is the
    IoU of its predicted box with its face, of the others 0) + DIoU loss
    on the positives weighted by their score, as GFL / SCRFD state them."""
    logits, dist = flatten_outputs(outputs)
    centres, strides, _sides = anchor_grid(frame_size, outputs["cls"][0].shape[-1])
    # in stride units, as published: the loss of a box does not grow with it
    unit = jnp.asarray(strides)[None, :, None]
    pred = distances_to_boxes(jnp.asarray(centres), jnp.asarray(strides), dist) / unit
    iou, diou = _iou_and_diou(pred, target / unit)
    posf = pos.astype(jnp.float32)
    quality = jax.lax.stop_gradient(iou) * posf
    prob = jax.nn.sigmoid(logits)
    bce = optax.sigmoid_binary_cross_entropy(logits, quality)
    num_pos = jnp.maximum(jnp.sum(posf), 1.0)
    cls_loss = jnp.sum(bce * jnp.abs(quality - prob) ** beta) / num_pos
    weight = jax.lax.stop_gradient(prob) * posf
    box_loss = jnp.sum(weight * diou) / jnp.maximum(jnp.sum(weight), 1e-6)
    return cls_loss + box_weight * box_loss


def make_scrfd_train_step(net: SCRFDNet, optimizer, frame_size: Tuple[int, int]):
    """One jitted step under batch-statistics BatchNorm; the stored
    moments in ``params`` are carried along untouched (their gradient is
    zero: nothing reads them under ``calibrate``)."""
    probe = net.clone(calibrate=True)

    @jax.jit  # ocvf-lint: boundary=jit-recompile-hazard -- offline training step, one fixed batch shape per train() call; never reached from the serving loop
    def step(params, opt_state, images, pos, target):
        def loss_fn(p):
            outputs, _sown = probe.apply({"params": p}, images,
                                         mutable=["batch_stats"])
            return scrfd_loss(outputs, pos, target, frame_size)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def train_scrfd(net: SCRFDNet, images: np.ndarray, boxes: np.ndarray,
                num_boxes: np.ndarray, *, steps: int = 300, batch_size: int = 8,
                learning_rate: float = 2e-3, seed: int = 0,
                params: Optional[Dict] = None, calibration_frames: int = 32,
                log_every: int = 0, losses: Optional[list] = None):
    """Train on (images [N, H, W] in [0, 255], padded yxyx boxes [N, B, 4],
    counts [N]); returns the parameters with the BatchNorms' moments of
    ``calibration_frames`` of the images stored."""
    frame_size = (int(images.shape[1]), int(images.shape[2]))
    pos, target = scrfd_targets(frame_size, boxes, num_boxes, net.num_anchors)
    if params is None:
        params = jax.jit(net.init)(
            jax.random.PRNGKey(seed), jnp.zeros((1, *frame_size)))["params"]
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, max(1, min(steps // 10, 100)), max(steps, 2))
    optimizer = optax.chain(optax.clip_by_global_norm(10.0), optax.adam(schedule))
    opt_state = optimizer.init(params)
    step = make_scrfd_train_step(net, optimizer, frame_size)
    n = images.shape[0]
    batch_size = min(batch_size, n)
    rng = np.random.default_rng(seed)
    for i in range(steps):
        idx = rng.choice(n, size=batch_size, replace=False)
        params, opt_state, loss = step(
            params, opt_state, jnp.asarray(images[idx], jnp.float32),
            jnp.asarray(pos[idx]), jnp.asarray(target[idx]))
        if losses is not None:
            losses.append(loss)
        if log_every and (i + 1) % log_every == 0:
            print(f"  scrfd step {i + 1}/{steps}: loss {float(loss):.4f}")  # ocvf-lint: boundary=host-sync -- offline training progress log; nothing here runs on the serving loop
    pick = rng.choice(n, size=min(calibration_frames, n), replace=False)
    return calibrate_batch_stats(net, params, jnp.asarray(images[pick], jnp.float32))


# ---- the detector class ----


class SCRFDDetector:
    """An ``SCRFDNet`` with its thresholds and parameters: the lifecycle of
    ``CNNFaceDetector`` (``train`` / ``detect_batch`` / ``detect`` / ``save``
    / ``load``) and the fused step's ``Detector`` boundary (``max_faces``,
    ``params``, ``detect_traced``)."""

    kind = KIND

    def __init__(
        self,
        stem_features: Sequence[int] = (28, 28, 56),
        stage_features: Sequence[int] = (56, 88, 88, 224),
        stage_blocks: Sequence[int] = (3, 4, 2, 3),
        neck_features: int = 56,
        head_features: int = 80,
        head_convs: int = 3,
        head_groups: int = 16,
        num_anchors: int = 2,
        strides_share: bool = True,
        max_faces: int = 8,
        score_threshold: float = 0.5,
        iou_threshold: float = 0.4,
        pre_nms: Optional[int] = None,
    ):
        self.net = SCRFDNet(
            stem_features=tuple(int(v) for v in stem_features),
            stage_features=tuple(int(v) for v in stage_features),
            stage_blocks=tuple(int(v) for v in stage_blocks),
            neck_features=int(neck_features), head_features=int(head_features),
            head_convs=int(head_convs), head_groups=int(head_groups),
            num_anchors=int(num_anchors), strides_share=bool(strides_share))
        self.max_faces = int(max_faces)
        self.score_threshold = float(score_threshold)
        self.iou_threshold = float(iou_threshold)
        self.pre_nms = int(pre_nms or 4 * self.max_faces)
        self._params: Optional[Dict] = None
        self._detect_jit = jax.jit(self.detect_traced)  # ocvf-lint: boundary=jit-recompile-hazard -- built ONCE at construction for the offline detect_batch path; serving compiles through RecognitionPipeline's cache-keyed builders

    def detect_traced(self, params, frames):
        """``(params, float32 frames [N, H, W]) -> (boxes [N, K, 4] pixel
        yxyx, scores [N, K], valid [N, K])``, to be traced inside a jitted
        step. Two sibling scopes: ``ocvf_detect`` round the net's forward,
        ``ocvf_decode`` round candidates and NMS."""
        with jax.named_scope("ocvf_detect"):
            outputs = self.net.apply({"params": params}, frames)
        with jax.named_scope("ocvf_decode"):
            return decode(outputs, tuple(frames.shape[1:3]), self.max_faces,
                          self.score_threshold, self.iou_threshold, self.pre_nms)

    def config(self) -> Dict[str, Any]:
        net = self.net
        return {
            "stem_features": list(net.stem_features),
            "stage_features": list(net.stage_features),
            "stage_blocks": list(net.stage_blocks),
            "neck_features": net.neck_features,
            "head_features": net.head_features, "head_convs": net.head_convs,
            "head_groups": net.head_groups, "num_anchors": net.num_anchors,
            "strides_share": net.strides_share, "max_faces": self.max_faces,
            "score_threshold": self.score_threshold,
            "iou_threshold": self.iou_threshold, "pre_nms": self.pre_nms,
        }

    def train(self, images, boxes, num_boxes, **kwargs):
        self._params = train_scrfd(self.net, images, boxes, num_boxes,
                                   params=self._params, **kwargs)
        return self

    def load_params(self, params) -> None:
        self._params = params

    @property
    def params(self):
        return self._params

    # -- checkpointing (msgpack, pickle-free, like CNNFaceDetector's) --

    def save(self, path: str) -> None:
        from flax import serialization as flax_serialization

        from opencv_facerecognizer_tpu.utils.serialization import atomic_write_bytes

        if self._params is None:
            raise RuntimeError("SCRFDDetector.save called before train()/load_params()")
        payload = {
            "header": {"format_version": 1, "kind": KIND, "eps": self.net.eps,
                       "config_json": json.dumps(self.config())},
            "params": jax.tree_util.tree_map(np.asarray, self._params),
        }
        atomic_write_bytes(path, flax_serialization.msgpack_serialize(payload))

    @classmethod
    def from_payload(cls, payload: Dict[str, Any]) -> "SCRFDDetector":
        det = cls(**json.loads(payload["header"]["config_json"]))
        det.load_params(jax.tree_util.tree_map(jnp.asarray, payload["params"]))
        return det

    @classmethod
    def load(cls, path: str) -> "SCRFDDetector":
        from flax import serialization as flax_serialization

        with open(path, "rb") as fh:
            payload = flax_serialization.msgpack_restore(fh.read())
        if payload["header"].get("kind") != KIND:
            raise ValueError(f"{path!r} is not an SCRFD checkpoint (its header "
                             f"names kind {payload['header'].get('kind')!r})")
        return cls.from_payload(payload)

    def detect_batch(self, images: jnp.ndarray):
        """[N, H, W] -> (boxes [N, K, 4] yxyx, scores [N, K], valid [N, K])
        on device; H and W are edge-padded up to the next multiple of 32
        and boxes clipped to the caller's extent."""
        if self._params is None:
            raise RuntimeError("SCRFDDetector.detect called before train()/load_params()")
        images = jnp.asarray(images, jnp.float32)
        h, w = images.shape[1], images.shape[2]
        ph, pw = (-h) % STRIDES[-1], (-w) % STRIDES[-1]
        if ph or pw:
            images = jnp.pad(images, ((0, 0), (0, ph), (0, pw)), mode="edge")
        boxes, scores, valid = self._detect_jit(self._params, images)
        return jnp.clip(boxes, 0.0, jnp.asarray([h, w, h, w], boxes.dtype)), scores, valid

    def detect(self, img: np.ndarray):
        """One grayscale image -> [(x0, y0, x1, y1)], as ``CNNFaceDetector.detect``."""
        boxes, _scores, valid = self.detect_batch(jnp.asarray(img, jnp.float32)[None])
        return [tuple(int(round(float(v))) for v in (b[1], b[0], b[3], b[2]))
                for b, ok in zip(np.asarray(boxes[0]), np.asarray(valid[0])) if ok]


def load_detector(path: str):
    """Either detector class, by the checkpoint's header: ``kind`` "scrfd"
    is this module's, a header that names none is ``CNNFaceDetector``'s."""
    from flax import serialization as flax_serialization

    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector

    with open(path, "rb") as fh:
        payload = flax_serialization.msgpack_restore(fh.read())
    if payload.get("header", {}).get("kind") == KIND:
        return SCRFDDetector.from_payload(payload)
    return CNNFaceDetector.load(path)


__all__ = ["SCRFDNet", "SCRFDDetector", "decode", "anchor_grid", "multiply_adds",
           "parameter_count", "random_params", "calibrate_batch_stats",
           "atss_assign", "scrfd_targets", "scrfd_loss", "train_scrfd",
           "load_detector", "KIND", "STRIDES", "VGA"]

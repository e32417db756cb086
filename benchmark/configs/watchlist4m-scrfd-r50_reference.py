"""Plain reference of the ``watchlist4m-scrfd-r50`` configuration.

The same mathematics as the serving path, written out in ``jax.numpy`` and
float32 at ``highest`` matmul precision: stage-1 gate, the SCRFD detector
(deep stem, four stages of basic blocks with inference-mode BatchNorm, the
path-aggregation neck, the shared GroupNorm head over three strides), its
anchor decode and greedy NMS in ``numpy``, bilinear crop, per-crop
standardization, the IResNet embedder, cosine top-1 over every gallery row.
No flax module, no kernels, no batching ladder, no cache, and nothing
imported from the program: the nets' parameters are read from their
checkpoint files with flax's msgpack reader (depths from the names of the
blocks, widths from the kernels' shapes, thresholds and epsilon from the
file's header), and the gallery rows it is handed are drawn again from the
seed by the benchmark's own generator once the program is gone
(``benchmark/run.py``), not read back from the program. Gate, crop,
standardize, IResNet and match are a copy of ``watchlist4m-r50_reference.py``'s:
the references stay independent of the program and of each other's edits.

``lower`` turns the reference into the control of "How correct is
decided": the same mathematics one precision step below the one the
configuration states. ``"nets+gallery"``: bf16 -> float8_e4m3 in every
convolution and the embedding head, int8 gallery rows and queries.
``"gallery"``: int8 rows and queries alone, the nets as stated.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STRIDES = (8, 16, 32)          # where C3, C4, C5 leave the backbone
Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8_e4m3 and back: the precision step below bf16."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def as_stored(rows) -> jnp.ndarray:
    """Gallery rows as the configuration keeps them: bf16, read as f32."""
    return jnp.asarray(rows, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)


def int8_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Symmetric int8 per row, dequantized."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    return jnp.round(x / scale).astype(jnp.int8).astype(jnp.float32) * scale


# ---- checkpoint files ----


def _restore(path: str) -> Dict[str, Any]:
    from flax import serialization

    with open(path, "rb") as fh:
        return serialization.msgpack_restore(fh.read())


def _nest(tree: Any) -> Any:
    """Checkpoints of ``utils.serialization`` keep a model's state under
    keys joined by "/": make nested dicts of them."""
    if not isinstance(tree, dict):
        return tree
    out: Dict[str, Any] = {}
    for key, value in tree.items():
        node = out
        *parents, leaf = str(key).split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = _nest(value)
    return out


def _find_tree(tree: Any, wanted: str) -> Any:
    """First sub-tree that holds the key ``wanted`` (depth first)."""
    if isinstance(tree, dict):
        if wanted in tree:
            return tree
        for value in tree.values():
            found = _find_tree(value, wanted)
            if found is not None:
                return found
    return None


def load_nets(nets_dir: str) -> Dict[str, Any]:
    det = _restore(f"{nets_dir}/detector.ckpt")
    gate = _restore(f"{nets_dir}/cascade.ckpt")
    emb = _restore(f"{nets_dir}/embedder.ckpt")
    emb_net = _find_tree(_nest(emb), "stem_conv")
    if emb_net is None:
        raise ValueError("embedder.ckpt holds no IResNet parameters")
    spec = json.loads(emb["header"]["spec_json"])
    to_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    if det["header"].get("kind") != "scrfd":
        raise ValueError("detector.ckpt is not an SCRFD checkpoint")
    return {
        "detector": to_f32(det["params"]),
        "detector_cfg": json.loads(det["header"]["config_json"]),
        "detector_eps": float(det["header"]["eps"]),
        "gate": to_f32(gate["params"]),
        "gate_cfg": json.loads(gate["header"]["config_json"]),
        "embedder": to_f32(emb_net),
        "embedder_eps": float(_find_tree(spec, "eps")["eps"]),
    }


# ---- layers ----


def conv(x, kernel, *, stride=1, padding="SAME", groups=1, bias=None,
         quant: Quant = None):
    if quant is not None:
        x, kernel = quant(x), quant(kernel)
    y = jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y if bias is None else y + bias


def group_norm(x, p, groups: int, eps: float = 1e-6):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


PAD1 = ((1, 1), (1, 1))  # the published 3x3 convolutions pad 1 on every side
PAD0 = ((0, 0), (0, 0))


def batch_norm(x, p, eps: float):
    """Inference-mode BatchNorm over the last axis: stored moments."""
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] + p["bias"]


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))


def avg_pool_2x2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def scrfd_forward(p, cfg, eps: float, frames, quant: Quant = None):
    """[N, H, W] float32 pixels -> (three [N, Hs, Ws, A] logit maps, three
    [N, Hs, Ws, A, 4] (left, top, right, bottom) distance maps in units of
    the level's stride), strides 8, 16, 32."""
    planes = p["stem_conv0"]["kernel"].shape[2]
    x = jnp.broadcast_to(frames[..., None], (*frames.shape, planes))
    x = (x - 127.5) / 128.0
    i = 0
    while f"stem_conv{i}" in p:
        x = conv(x, p[f"stem_conv{i}"]["kernel"], stride=2 if i == 0 else 1,
                 padding=PAD1, quant=quant)
        x = jax.nn.relu(batch_norm(x, p[f"stem_bn{i}"], eps))
        i += 1
    x = max_pool_3x3_s2(x)
    taps, stage = [], 1
    while f"stage{stage}_block0" in p:
        block = 0
        while f"stage{stage}_block{block}" in p:
            b = p[f"stage{stage}_block{block}"]
            # the first block of every stage but the first halves the extent
            stride = 2 if block == 0 and stage > 1 else 1
            y = conv(x, b["conv1"]["kernel"], stride=stride, padding=PAD1, quant=quant)
            y = jax.nn.relu(batch_norm(y, b["bn1"], eps))
            y = conv(y, b["conv2"]["kernel"], padding=PAD1, quant=quant)
            y = batch_norm(y, b["bn2"], eps)
            if "shortcut_conv" in b:
                if stride == 2:
                    x = avg_pool_2x2(x)
                x = conv(x, b["shortcut_conv"]["kernel"], padding=PAD0, quant=quant)
                x = batch_norm(x, b["shortcut_bn"], eps)
            x = jax.nn.relu(y + x)
            block += 1
        taps.append(x)
        stage += 1

    def biased(x, q, **kw):
        return conv(x, q["kernel"], bias=q["bias"], quant=quant, **kw)

    # the path-aggregation neck over C3, C4, C5: no norm, no activation
    lat = [biased(t, p[f"neck_lateral{i}"], padding=PAD0)
           for i, t in enumerate(taps[1:])]
    for i in range(len(lat) - 1, 0, -1):
        lat[i - 1] = lat[i - 1] + jnp.repeat(jnp.repeat(lat[i], 2, axis=1), 2, axis=2)
    outs = [biased(v, p[f"neck_fpn{i}"], padding=PAD1) for i, v in enumerate(lat)]
    for i in range(len(outs) - 1):
        outs[i + 1] = outs[i + 1] + biased(outs[i], p[f"neck_down{i}"], stride=2,
                                           padding=PAD1)
    levels = [outs[0]] + [biased(v, p[f"neck_pafpn{i}"], padding=PAD1)
                          for i, v in enumerate(outs[1:])]

    a = int(cfg["num_anchors"])
    cls, reg = [], []
    for lv, feat in enumerate(levels):
        tag = "" if cfg["strides_share"] else f"{lv}_"
        for i in range(int(cfg["head_convs"])):
            feat = conv(feat, p[f"head_{tag}conv{i}"]["kernel"], padding=PAD1,
                        quant=quant)
            feat = jax.nn.relu(group_norm(feat, p[f"head_{tag}gn{i}"],
                                          int(cfg["head_groups"]), eps))
        cls.append(biased(feat, p[f"head_{tag}cls"], padding=PAD1))
        dist = p[f"head_scale{lv}"] * biased(feat, p[f"head_{tag}reg"], padding=PAD1)
        reg.append(dist.reshape((*dist.shape[:3], a, 4)))
    return tuple(cls), tuple(reg)


def gate_forward(p, cfg, frames, quant: Quant = None):
    """[N, H, W] float32 pixels -> [N] face-possible probability."""
    x = frames[..., None] / 255.0
    d = int(cfg["downsample"])
    if d > 1:
        n, h, w, c = x.shape
        x = x.reshape(n, h // d, d, w // d, d, c).mean(axis=(2, 4))
    for i, feats in enumerate(cfg["features"]):
        x = conv(x, p[f"Conv_{i}"]["kernel"], stride=2, quant=quant)
        x = jax.nn.relu(group_norm(x, p[f"GroupNorm_{i}"], min(4, int(feats))))
    last = p[f"Conv_{len(cfg['features'])}"]
    logits = conv(x, last["kernel"], bias=last["bias"], quant=quant)[..., 0]
    return jax.nn.sigmoid(jnp.max(logits, axis=(1, 2)))


def prelu(x, p):
    return jnp.where(x >= 0, x, p["slope"] * x)


def embedder_forward(p, eps: float, crops, quant: Quant = None):
    """[M, h, w] standardized crops -> [M, E] unit embeddings."""
    planes = p["stem_conv"]["kernel"].shape[2]
    x = jnp.broadcast_to(crops[..., None], (*crops.shape, planes))
    x = conv(x, p["stem_conv"]["kernel"], padding=PAD1, quant=quant)
    x = prelu(batch_norm(x, p["stem_bn"], eps), p["stem_prelu"])
    stage = 1
    while f"stage{stage}_block0" in p:
        block = 0
        while f"stage{stage}_block{block}" in p:
            b = p[f"stage{stage}_block{block}"]
            # the first block of a stage halves the extent and has a
            # shortcut of its own; the others keep both and add x itself
            stride = 2 if block == 0 else 1
            y = batch_norm(x, b["bn1"], eps)
            y = conv(y, b["conv1"]["kernel"], padding=PAD1, quant=quant)
            y = prelu(batch_norm(y, b["bn2"], eps), b["prelu"])
            y = conv(y, b["conv2"]["kernel"], stride=stride, padding=PAD1,
                     quant=quant)
            y = batch_norm(y, b["bn3"], eps)
            if "shortcut_conv" in b:
                x = conv(x, b["shortcut_conv"]["kernel"], stride=stride,
                         padding="VALID", quant=quant)
                x = batch_norm(x, b["shortcut_bn"], eps)
            x = y + x
            block += 1
        stage += 1
    x = batch_norm(x, p["head_bn"], eps)
    x = x.reshape(x.shape[0], -1)
    w = p["fc_kernel"]
    if quant is not None:
        x, w = quant(x), quant(w)
    x = jnp.dot(x, w, precision=HIGHEST) + p["fc_bias"]
    x = batch_norm(x, p["features_bn"], eps)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def crop_resize(frames, boxes, size: Tuple[int, int]):
    """Bilinear crop of [N, K] boxes (pixel yxyx) to ``size``: sample
    centres spread over the box, taps clamped to the frame."""
    n, h, w = frames.shape
    oh, ow = size
    ty = (jnp.arange(oh, dtype=jnp.float32) + 0.5) / oh
    tx = (jnp.arange(ow, dtype=jnp.float32) + 0.5) / ow
    y0, x0, y1, x1 = (boxes[..., i] for i in range(4))
    ys = jnp.clip(y0[..., None] + (y1 - y0)[..., None] * ty - 0.5, 0.0, h - 1.0)
    xs = jnp.clip(x0[..., None] + (x1 - x0)[..., None] * tx - 0.5, 0.0, w - 1.0)
    ay = jnp.maximum(0.0, 1.0 - jnp.abs(ys[..., None] - jnp.arange(h, dtype=jnp.float32)))
    ax = jnp.maximum(0.0, 1.0 - jnp.abs(xs[..., None] - jnp.arange(w, dtype=jnp.float32)))
    tmp = jnp.einsum("nkih,nhw->nkiw", ay, frames, precision=HIGHEST)
    return jnp.einsum("nkiw,nkjw->nkij", tmp, ax, precision=HIGHEST)


def standardize(crops):
    mean = jnp.mean(crops, axis=(-2, -1), keepdims=True)
    std = jnp.maximum(jnp.std(crops, axis=(-2, -1), keepdims=True), 1e-6)
    return (crops - mean) / std


# ---- decode (host side, frame by frame: it is a reference) ----


def _iou(a: np.ndarray, b: np.ndarray) -> float:
    iy = max(0.0, min(a[2], b[2]) - max(a[0], b[0]))
    ix = max(0.0, min(a[3], b[3]) - max(a[1], b[1]))
    inter = iy * ix
    area = lambda r: max(r[2] - r[0], 0.0) * max(r[3] - r[1], 0.0)  # noqa: E731
    return inter / max(area(a) + area(b) - inter, 1e-12)


def anchor_grid(frame_size: Tuple[int, int], num_anchors: int):
    """(centres [A, 2] (y, x) pixels, stride of each anchor [A]) in the
    order the maps flatten: level, row, column, anchor. An anchor's centre
    is its cell's corner (x * stride, y * stride); the anchors of one cell
    differ in size alone, which only the training's assignment reads."""
    centres, strides = [], []
    for stride in STRIDES:
        hs, ws = frame_size[0] // stride, frame_size[1] // stride
        yy, xx = np.mgrid[0:hs, 0:ws].astype(np.float64) * stride
        c = np.repeat(np.stack([yy, xx], axis=-1).reshape(-1, 2), num_anchors, axis=0)
        centres.append(c)
        strides.append(np.full((len(c),), float(stride)))
    return np.concatenate(centres), np.concatenate(strides)


def decode(cls, reg, cfg: Dict[str, Any], frame_size: Tuple[int, int]):
    """One frame's maps (three logit maps, three distance maps) -> (boxes
    [K, 4], scores [K], valid [K]) with K = max_faces, best first: the
    ``pre_nms`` best-scored anchors of all levels, a box from each anchor's
    centre and its four distances times the stride, greedy NMS over those
    above the threshold, clamp to the frame."""
    max_faces = int(cfg["max_faces"])
    a = int(cfg["num_anchors"])
    logits = np.concatenate([np.asarray(c, np.float64).reshape(-1) for c in cls])
    dist = np.concatenate([np.asarray(r, np.float64).reshape(-1, 4) for r in reg])
    scores = 1.0 / (1.0 + np.exp(-logits))
    centres, strides = anchor_grid(frame_size, a)
    order = np.argsort(-scores, kind="stable")[:min(int(cfg["pre_nms"]), len(scores))]
    kept = []
    for idx in order:  # in descending score order
        if scores[idx] <= cfg["score_threshold"]:
            continue
        (cy, cx), (left, top, right, bottom) = centres[idx], dist[idx] * strides[idx]
        box = np.array([cy - top, cx - left, cy + bottom, cx + right], np.float64)
        if all(_iou(box, other) <= cfg["iou_threshold"] for _s, other in kept):
            kept.append((scores[idx], box))
    kept = kept[:max_faces]
    boxes = np.zeros((max_faces, 4), np.float32)
    out_scores = np.full((max_faces,), -np.inf, np.float32)
    valid = np.zeros((max_faces,), bool)
    lim = np.array([frame_size[0], frame_size[1]] * 2, np.float64)
    for j, (score, box) in enumerate(kept):
        boxes[j] = np.clip(box, 0.0, lim)
        out_scores[j] = score
        valid[j] = True
    return boxes, out_scores, valid


# ---- the whole path, in blocks ----


class Reference:
    """Holds the nets; every method takes and returns host arrays."""

    LOWER = {None: (False, False), "nets+gallery": (True, True),
             "gallery": (False, True)}

    def __init__(self, nets_dir: str, face_size: Tuple[int, int],
                 lower: Optional[str] = None):
        self.nets = load_nets(nets_dir)
        self.face_size = tuple(face_size)
        self.lower_nets, self.lower_gallery = self.LOWER[lower]
        quant = self._quant = fp8 if self.lower_nets else None
        nets = self.nets
        self._gate = jax.jit(lambda f: gate_forward(
            nets["gate"], nets["gate_cfg"], f, quant))
        # parameters are arguments of the programs, not constants in them
        self._detect = jax.jit(lambda p, f: scrfd_forward(
            p, nets["detector_cfg"], nets["detector_eps"], f, quant))
        # The embedder's 174 MB of parameters are arguments of its two
        # programs, not constants inside them: baked in, each compiles for
        # most of a minute.
        self._embed = jax.jit(lambda p, f, b: embedder_forward(
            p, nets["embedder_eps"],
            standardize(crop_resize(f, b, self.face_size)).reshape(
                (-1, *self.face_size)), quant))
        self._embed_images = jax.jit(lambda p, c: embedder_forward(
            p, nets["embedder_eps"], standardize(c), quant))

    as_stored = staticmethod(as_stored)

    @property
    def gate_threshold(self) -> float:
        return float(self.nets["gate_cfg"].get("threshold", 0.3))

    def gate_scores(self, frames: np.ndarray, block: int = 16) -> np.ndarray:
        out = [np.asarray(self._gate(jnp.asarray(frames[i:i + block], jnp.float32)))
               for i in range(0, len(frames), block)]
        return np.concatenate(out) if out else np.zeros((0,), np.float32)

    def detect(self, frames: np.ndarray, block: int = 8):
        boxes, scores, valid = [], [], []
        size = tuple(frames.shape[1:3])
        for i in range(0, len(frames), block):
            cls, reg = self._detect(self.nets["detector"],
                                    jnp.asarray(frames[i:i + block], jnp.float32))
            cls = [np.asarray(c) for c in cls]
            reg = [np.asarray(r) for r in reg]
            for j in range(len(cls[0])):
                b, s, v = decode([c[j] for c in cls], [r[j] for r in reg],
                                 self.nets["detector_cfg"], size)
                boxes.append(b), scores.append(s), valid.append(v)
        return np.stack(boxes), np.stack(scores), np.stack(valid)

    def embed(self, frames: np.ndarray, boxes: np.ndarray,
              block: int = 8) -> np.ndarray:
        """[N, H, W] frames and [N, K, 4] boxes -> [N, K, E]."""
        out = []
        for i in range(0, len(frames), block):
            f = jnp.asarray(frames[i:i + block], jnp.float32)
            b = jnp.asarray(boxes[i:i + block], jnp.float32)
            out.append(np.asarray(self._embed(self.nets["embedder"], f, b))
                       .reshape((f.shape[0], boxes.shape[1], -1)))
        return np.concatenate(out)

    def embed_images(self, images: np.ndarray) -> np.ndarray:
        """Enrolment images at the embedder's own size -> [M, E]."""
        return np.asarray(self._embed_images(
            self.nets["embedder"], jnp.asarray(images, jnp.float32)))

    def match(self, queries: np.ndarray, rows, skip_head: int,
              head_rows: np.ndarray, block_rows: int):
        """Cosine top-1 of [Q, E] queries over ``head_rows`` (the enrolled
        rows, float32, standing for gallery rows 0..skip_head) and
        ``rows[skip_head:]`` (bf16 on the device). Returns (best sims [Q],
        best row index [Q]) and a function giving the sims at named rows."""
        q = jnp.asarray(queries, jnp.float32)
        lower = self.lower_gallery
        if lower:
            q = int8_rows(q)
        n = rows.shape[0]

        @jax.jit
        def block_best(q, rows, start):
            g = jax.lax.dynamic_slice_in_dim(rows, start, block_rows, 0)
            g = g.astype(jnp.float32)
            if lower:
                g = int8_rows(g)
            s = jnp.dot(q, g.T, precision=HIGHEST)
            idx = start + jnp.arange(block_rows)
            s = jnp.where(idx[None, :] >= skip_head, s, -jnp.inf)
            return jnp.max(s, axis=1), start + jnp.argmax(s, axis=1)

        best = np.full((len(queries),), -np.inf, np.float32)
        best_idx = np.full((len(queries),), -1, np.int64)
        if len(head_rows):
            head = as_stored(head_rows)  # kept in bf16 like every row
            if lower:
                head = int8_rows(head)
            s = np.asarray(jnp.dot(q, head.T, precision=HIGHEST))
            best, best_idx = s.max(axis=1), s.argmax(axis=1).astype(np.int64)
        for start in range(0, n, block_rows):
            if start + block_rows <= skip_head:
                continue
            vals, idx = (np.asarray(a) for a in block_best(q, rows, start))
            better = vals > best
            best = np.where(better, vals, best)
            best_idx = np.where(better, idx, best_idx)

        def sims_at(row_index: np.ndarray) -> np.ndarray:
            """Sim of query i with gallery row ``row_index[i]`` (>= skip_head)."""
            g = jnp.take(rows, jnp.asarray(row_index), axis=0).astype(jnp.float32)
            if lower:
                g = int8_rows(g)
            return np.asarray(jnp.sum(q * g, axis=-1))

        return best, best_idx, sims_at

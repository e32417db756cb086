"""Plain reference of the ``watchlist4m-vitb`` configuration.

The same mathematics as the serving path, written out in ``jax.numpy`` and
float32 at ``highest`` matmul precision: stage-1 gate, center-heatmap
detector with its decode and greedy NMS, bilinear crop, per-crop
standardization, the vision-transformer embedder (9x9 stride-9 patch
embedding over the whole patches of the crop, learned positions, pre-norm
blocks of multi-head attention written with plain ``einsum`` and a
ReLU6 MLP, last LayerNorm, token-major flatten, linear -> BatchNorm ->
linear -> BatchNorm head with stored moments, L2 norm), cosine top-1 over
every gallery row. No flax module, no kernels, no batching ladder, no cache,
and nothing imported from the program: the nets' parameters are read from
their checkpoint files with flax's msgpack reader (the embedder's depth from
the names of its blocks; its heads, patch and epsilons from the file's
header), and the gallery rows it is handed are drawn again from the seed by
the benchmark's own generator once the program is gone
(``benchmark/run.py``), not read back from the program. Gate, detector, crop,
decode, match and ``as_stored`` are a copy of
``watchlist4m-r50_reference.py``'s (the harness finds a reference by the
configuration's name): the references stay independent of the program and
of each other's edits.

``lower`` turns the reference into the control of "How correct is
decided": the same mathematics one precision step below the one the
configuration states. ``"nets+gallery"``: bf16 -> float8_e4m3 operands in
every convolution of gate and detector and in EVERY matmul of the embedder
(patch, qkv, q k^T, A v, proj, both MLP layers, the head's two), int8
gallery rows and queries. ``"gallery"``: int8 rows and queries alone, the
nets as stated.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STRIDE = 8
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
    emb_net = _find_tree(_nest(emb), "patch_embed")
    if emb_net is None:
        raise ValueError("embedder.ckpt holds no ViT parameters")
    spec = _find_tree(json.loads(emb["header"]["spec_json"]), "heads")
    to_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    return {
        "detector": to_f32(det["params"]),
        "detector_cfg": json.loads(det["header"]["config_json"]),
        "gate": to_f32(gate["params"]),
        "gate_cfg": json.loads(gate["header"]["config_json"]),
        "embedder": to_f32(emb_net),
        "embedder_cfg": {"heads": int(spec["heads"]), "patch": int(spec["patch"]),
                         "layer_norm_eps": float(spec["layer_norm_eps"]),
                         "head_bn_eps": float(spec["head_bn_eps"])},
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


def space_to_depth(x, s: int):
    n, h, w, c = x.shape
    x = x.reshape(n, h // s, s, w // s, s, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // s, w // s, s * s * c)


def detector_forward(p, cfg, frames, quant: Quant = None):
    """[N, H, W] float32 pixels -> heatmap logits, size, offset maps."""
    x = frames[..., None] / 255.0
    s = int(cfg.get("space_to_depth", 1))
    if s > 1:
        x = space_to_depth(x, s)
    remaining, accum, i = STRIDE // s, 1, 0
    for _feats in cfg["features"]:
        stride = 2 if accum < remaining else 1
        accum *= stride
        x = conv(x, p[f"Conv_{i}"]["kernel"], stride=stride, quant=quant)
        x = jax.nn.relu(group_norm(x, p[f"GroupNorm_{i}"], 4))
        x = conv(x, p[f"Conv_{i + 1}"]["kernel"], quant=quant)
        x = jax.nn.relu(group_norm(x, p[f"GroupNorm_{i + 1}"], 4))
        i += 2
    head = p[f"Conv_{i}"]
    h = jax.nn.relu(conv(x, head["kernel"], bias=head["bias"], quant=quant))
    out = []
    for j in (1, 2, 3):
        q = p[f"Conv_{i + j}"]
        out.append(conv(h, q["kernel"], bias=q["bias"], quant=quant))
    return out[0][..., 0], out[1], out[2]


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


def batch_norm(x, p, eps: float):
    """Inference-mode BatchNorm over the last axis: stored moments."""
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] + p["bias"]


def layer_norm(x, p, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def matmul(spec: str, a, b, quant: Quant = None):
    """One matmul of the embedder, written as an ``einsum``: both operands
    through ``quant`` where the control lowers them."""
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def linear(x, p, quant: Quant = None):
    y = matmul("...i,io->...o", x, p["kernel"], quant)
    return y + p["bias"] if "bias" in p else y


def attention(q, k, v, quant: Quant = None):
    """[M, T, H, D] queries, keys and values -> [M, T, H, D]: softmax of
    q k^T / sqrt(D) over the keys, times v, every head on its own."""
    scores = matmul("mqhd,mkhd->mhqk", q, k, quant) / np.sqrt(q.shape[-1])
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return matmul("mhqk,mkhd->mqhd", weights, v, quant)


def embedder_forward(p, cfg: Dict[str, Any], crops, quant: Quant = None):
    """[M, h, w] standardized crops -> [M, E] unit embeddings."""
    heads, patch = cfg["heads"], cfg["patch"]
    planes = p["patch_embed"]["kernel"].shape[0] // (patch * patch)
    m, h, w = crops.shape
    gh, gw = h // patch, w // patch
    # the stride-9, pad-0 convolution as a matmul over whole patches: the
    # rows and columns past the last whole patch are never read
    x = jnp.broadcast_to(crops[..., None], (m, h, w, planes))
    x = x[:, :gh * patch, :gw * patch].reshape(m, gh, patch, gw, patch, planes)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(m, gh * gw, patch * patch * planes)
    x = linear(x, p["patch_embed"], quant) + p["pos_embed"]
    tokens, width = x.shape[1:]
    depth = 0
    while f"block{depth}" in p:
        b = p[f"block{depth}"]
        qkv = linear(layer_norm(x, b["norm1"], cfg["layer_norm_eps"]), b["qkv"], quant)
        qkv = qkv.reshape(m, tokens, 3, heads, width // heads)
        y = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], quant)
        x = x + linear(y.reshape(m, tokens, width), b["proj"], quant)
        y = linear(layer_norm(x, b["norm2"], cfg["layer_norm_eps"]), b["fc1"], quant)
        x = x + linear(jnp.clip(y, 0.0, 6.0), b["fc2"], quant)  # ReLU6
        depth += 1
    x = layer_norm(x, p["norm"], cfg["layer_norm_eps"]).reshape(m, -1)
    x = batch_norm(linear(x, p["feature_fc1"], quant), p["feature_bn1"],
                   cfg["head_bn_eps"])
    x = batch_norm(linear(x, p["feature_fc2"], quant), p["feature_bn2"],
                   cfg["head_bn_eps"])
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


def decode(heat_logits: np.ndarray, size: np.ndarray, offset: np.ndarray,
           cfg: Dict[str, Any]):
    """One frame's maps -> (boxes [K, 4], scores [K], valid [K]) with
    K = max_faces, best first: 3x3 peak suppression, the 4K strongest
    peaks, box assembly, greedy NMS, clamp to the frame."""
    max_faces = int(cfg["max_faces"])
    heat = 1.0 / (1.0 + np.exp(-heat_logits.astype(np.float64)))
    hs, ws = heat.shape
    padded = np.pad(heat, 1, constant_values=-np.inf)
    pooled = np.max([padded[dy:dy + hs, dx:dx + ws]
                     for dy in range(3) for dx in range(3)], axis=0)
    peaks = np.where(heat >= pooled - 1e-6, heat, 0.0).reshape(-1)
    k = min(max_faces * 4, hs * ws)
    order = np.argsort(-peaks, kind="stable")[:k]
    cand = []
    for idx in order:
        cy, cx = divmod(int(idx), ws)
        off, sz = offset.reshape(-1, 2)[idx], size.reshape(-1, 2)[idx]
        cyf, cxf = cy + off[0], cx + off[1]
        bh, bw = max(sz[0], 1e-3), max(sz[1], 1e-3)
        cand.append((peaks[idx], np.array(
            [(cyf - bh / 2) * STRIDE, (cxf - bw / 2) * STRIDE,
             (cyf + bh / 2) * STRIDE, (cxf + bw / 2) * STRIDE], np.float64)))
    kept = []
    for score, box in cand:  # already in descending score order
        if score <= cfg["score_threshold"]:
            continue
        if all(_iou(box, other) <= cfg["iou_threshold"] for _s, other in kept):
            kept.append((score, box))
    kept = kept[:max_faces]
    boxes = np.zeros((max_faces, 4), np.float32)
    scores = np.full((max_faces,), -np.inf, np.float32)
    valid = np.zeros((max_faces,), bool)
    lim = np.array([hs * STRIDE, ws * STRIDE] * 2, np.float64)
    for j, (score, box) in enumerate(kept):
        boxes[j] = np.clip(box, 0.0, lim)
        scores[j] = score
        valid[j] = True
    return boxes, scores, valid


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
        self._detect = jax.jit(lambda f: detector_forward(
            nets["detector"], nets["detector_cfg"], f, quant))
        # The embedder's 455 MB of parameters are arguments of its two
        # programs, not constants inside them: baked in, each compiles for
        # minutes.
        self._embed = jax.jit(lambda p, f, b: embedder_forward(
            p, nets["embedder_cfg"],
            standardize(crop_resize(f, b, self.face_size)).reshape(
                (-1, *self.face_size)), quant))
        self._embed_images = jax.jit(lambda p, c: embedder_forward(
            p, nets["embedder_cfg"], standardize(c), quant))

    as_stored = staticmethod(as_stored)

    @property
    def gate_threshold(self) -> float:
        return float(self.nets["gate_cfg"].get("threshold", 0.3))

    def gate_scores(self, frames: np.ndarray, block: int = 32) -> np.ndarray:
        out = [np.asarray(self._gate(jnp.asarray(frames[i:i + block], jnp.float32)))
               for i in range(0, len(frames), block)]
        return np.concatenate(out) if out else np.zeros((0,), np.float32)

    def detect(self, frames: np.ndarray, block: int = 32):
        boxes, scores, valid = [], [], []
        for i in range(0, len(frames), block):
            heat, size, offset = (np.asarray(a) for a in self._detect(
                jnp.asarray(frames[i:i + block], jnp.float32)))
            for j in range(len(heat)):
                b, s, v = decode(heat[j], size[j], offset[j],
                                 self.nets["detector_cfg"])
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

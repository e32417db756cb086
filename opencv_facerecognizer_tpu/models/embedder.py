"""CNN face embedder (Flax): the TPU-native replacement for the reference's
subspace projections on the north-star path (BASELINE.json:5: "feature
.compute() (PCA/LDA/LBP projection) is swapped for a FaceNet-style embedding
CNN compiled via XLA"; PAPERS.md:8 multibatch metric embedding).

Design, TPU-first:
- MobileFaceNet-style separable-conv net ending in a global depthwise conv
  and a linear embedding head, L2-normalized. Compute in bfloat16 (MXU),
  params in float32.
- Training uses an ArcFace (additive angular margin) softmax head — the
  strongest-known recipe for verification accuracy at this model size —
  with an optax train step under ``jit``; the whole epoch loop is host-side
  only over device-resident batches.
- ``CNNEmbedding`` adapts the trained net to the ``AbstractFeature``
  boundary, so ``PredictableModel(CNNEmbedding(...), NearestNeighbor(
  CosineDistance()))`` is exactly the reference's model composition with the
  CNN swapped in — the plugin gating the north star demands.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from opencv_facerecognizer_tpu.models.feature import AbstractFeature
from opencv_facerecognizer_tpu.ops import image as image_ops


class _SepBlock(nn.Module):
    """Depthwise-separable conv block with optional stride + residual.

    ``norm="light"`` drops the GroupNorm between the depthwise and
    pointwise convs (keeping the ReLU): each GroupNorm is a cross-channel
    reduction the VPU runs between MXU calls, and at 2 per block they are
    pure inter-matmul stall time. Measured (scripts/explore_perf.py r4):
    the light scheme is what lifted the separable net's MFU; training
    stability is covered by the remaining per-block GroupNorm.
    """

    features: int
    stride: int = 1
    norm: str = "full"
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        inp = x
        ch = x.shape[-1]
        x = nn.Conv(
            ch, (3, 3), strides=(self.stride, self.stride),
            feature_group_count=ch, use_bias=False, dtype=self.dtype,
        )(x)
        if self.norm == "full":
            x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(x)
        x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
        if self.stride == 1 and ch == self.features:
            x = x + inp
        return nn.relu(x)


class _DenseBlock(nn.Module):
    """Plain 3x3 conv block with optional stride + residual.

    The MXU-friendly alternative to ``_SepBlock``: a depthwise 3x3 is
    VPU-bound (one lane per channel), while a dense 3x3 at these channel
    widths is a batched matmul the systolic array runs near peak — ~8x the
    FLOPs but measured wall-clock competitive, with more model capacity."""

    features: int
    stride: int = 1
    norm: str = "full"  # dense blocks have one norm either way
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        inp = x
        x = nn.Conv(self.features, (3, 3), strides=(self.stride, self.stride),
                    use_bias=False, dtype=self.dtype)(x)
        x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
        if self.stride == 1 and inp.shape[-1] == self.features:
            x = x + inp
        return nn.relu(x)


#: The serving-default embedder: the HARD-protocol accuracy-gated
#: structure at the GATED input resolution. Round-4 measurements
#: (scripts/.gate_embedder.jsonl, scripts/explore_perf.py):
#: - every stem structural speedup (space_to_depth 2/4, light norm, dense
#:   blocks) measured BELOW the baseline structure's verification accuracy
#:   at equal training (0.9655-0.9902 vs 0.9937 @ 9000 steps), so the
#:   structure stays s1/full/separable;
#: - the >=0.99 north-star numbers (0.9943 +/- 0.0020, fold_min 0.9917 @
#:   30000 steps, batch 192) are measured AT 64x64 INPUT — serving crops
#:   at 112x112 was never accuracy-justified, and embedding at the gated
#:   64x64 cuts the embed+crop stage cost ~3x with no accuracy claim lost.
SERVING_EMBEDDER_KWARGS = dict(
    embed_dim=256,
    stem_features=32,
    stage_features=(64, 128, 256),
    stage_blocks=(2, 2, 2),
    block="separable",
    space_to_depth=1,
    norm="full",
)
#: the accuracy protocol's input resolution — serving crops to the same
SERVING_FACE_SIZE = (64, 64)


class FaceEmbedNet(nn.Module):
    """MobileFaceNet-lite: stem conv -> conv stages -> global depthwise
    conv -> linear embedding, L2-normalized.

    ``stage_features``/``stage_blocks`` scale the net: the default is sized
    for one v5e chip at batch 256; tests use a tiny variant. ``block``
    picks the stage op: "separable" (depthwise+pointwise, fewer FLOPs,
    VPU-heavy) or "dense" (plain 3x3 convs, MXU-native).

    ``space_to_depth`` folds an s x s pixel block into s^2 input channels
    before the stem conv (lossless) — the same MXU-starving-stem fix the
    detector uses (detector.py:46-50): a 1-input-channel conv at 112x112
    feeds the 128-lane systolic array 9 rows of work per tile. The net's
    TOTAL downsample (2^(1 + len(stages))) is preserved: stem/stage
    strides drop to 1 once the folding already covered them, so the final
    spatial extent (and the GDC kernel) is identical for every setting.
    ``norm`` ("full" | "light") picks the per-block norm scheme (see
    ``_SepBlock``).
    """

    embed_dim: int = 128
    stem_features: int = 32
    stage_features: Sequence[int] = (64, 128, 128)
    stage_blocks: Sequence[int] = (2, 2, 2)
    block: str = "separable"
    space_to_depth: int = 1
    norm: str = "full"
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # [N, H, W] grayscale or [N, H, W, C]
        if x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.dtype)
        total_stride = 2 ** (1 + len(self.stage_features))
        s = int(self.space_to_depth)
        if s > 1:
            if total_stride % s:
                raise ValueError(
                    f"space_to_depth={s} must divide the net's total "
                    f"downsample {total_stride}"
                )
            n, h, w, c = x.shape
            if h % s or w % s:
                raise ValueError(
                    f"input {h}x{w} not divisible by space_to_depth={s}"
                )
            x = x.reshape(n, h // s, s, w // s, s, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // s, w // s, s * s * c)
        remaining = total_stride // s
        accum = 1
        stem_stride = 2 if accum < remaining else 1
        accum *= stem_stride
        x = nn.Conv(self.stem_features, (3, 3),
                    strides=(stem_stride, stem_stride), use_bias=False,
                    dtype=self.dtype)(x)
        x = nn.GroupNorm(num_groups=4, dtype=self.dtype)(x)
        x = nn.relu(x)
        block_cls = {"separable": _SepBlock, "dense": _DenseBlock}[self.block]
        for feats, blocks in zip(self.stage_features, self.stage_blocks):
            stride = 2 if accum < remaining else 1
            accum *= stride
            x = block_cls(feats, stride=stride, norm=self.norm,
                          dtype=self.dtype)(x)
            for _ in range(blocks - 1):
                x = block_cls(feats, stride=1, norm=self.norm,
                              dtype=self.dtype)(x)
        # Global depthwise conv (GDC): one weight per spatial position/channel.
        h, w, c = x.shape[1], x.shape[2], x.shape[3]
        x = nn.Conv(c, (h, w), padding="VALID", feature_group_count=c,
                    use_bias=False, dtype=self.dtype)(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.embed_dim, use_bias=True, dtype=self.dtype)(x)
        x = x.astype(jnp.float32)
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def fused_forward(net: "FaceEmbedNet", params: Dict[str, Any],
                  x: jnp.ndarray, *, interpret: bool = False,
                  block_b: int = 8) -> jnp.ndarray:
    """Serving-only fused forward of a separable ``FaceEmbedNet``: same
    params, same math, different schedule.

    Stage blocks run as one pallas call each (``ops.pallas_sepblock`` —
    the activation never leaves VMEM inside a block, and the depthwise
    conv avoids XLA's grouped-conv lowering); the GDC runs as an einsum
    (``nhwc,hwc->nc`` — a multiply+reduce instead of a C-group grouped
    convolution); stem conv and embedding head stay XLA (dense convs and
    matmuls are already MXU-native). Training and the accuracy gate keep
    the flax graph — this path only re-schedules inference, and
    tests/test_pallas_sepblock.py pins the numerical equivalence
    (cosine > 0.9999 against ``net.apply``).

    Mirrors ``FaceEmbedNet.__call__``'s stride/naming scheme exactly
    (params: Conv_0/GroupNorm_0 stem, _SepBlock_i blocks, Conv_1 GDC,
    Dense_0 head); raises for configs it does not cover rather than
    silently diverging.
    """
    if net.block != "separable":
        raise ValueError("fused_forward covers block='separable' only")
    if net.norm != "full":
        raise ValueError("fused_forward covers norm='full' only")
    from opencv_facerecognizer_tpu.ops.pallas_sepblock import fused_sep_block

    dtype = net.dtype
    if x.ndim == 3:
        x = x[..., None]
    x = x.astype(dtype)
    total_stride = 2 ** (1 + len(net.stage_features))
    s = int(net.space_to_depth)
    if s > 1:
        n, h, w, c = x.shape
        x = x.reshape(n, h // s, s, w // s, s, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // s, w // s, s * s * c)
    remaining = total_stride // s
    accum = 1
    stem_stride = 2 if accum < remaining else 1
    accum *= stem_stride

    x = jax.lax.conv_general_dilated(
        x.astype(dtype), params["Conv_0"]["kernel"].astype(dtype),
        window_strides=(stem_stride, stem_stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # the stem norm IS the flax module (same graph, no duplicated math —
    # only the stage blocks get the pallas schedule)
    x = nn.GroupNorm(num_groups=4, dtype=dtype).apply(
        {"params": params["GroupNorm_0"]}, x)
    x = jnp.maximum(x, 0.0).astype(dtype)

    i = 0
    for feats, blocks in zip(net.stage_features, net.stage_blocks):
        for b in range(blocks):
            stride = 2 if (b == 0 and accum < remaining) else 1
            if b == 0:
                accum *= stride
            p = params[f"_SepBlock_{i}"]
            in_ch = x.shape[-1]
            x = fused_sep_block(
                x,
                p["Conv_0"]["kernel"], p["GroupNorm_0"]["scale"],
                p["GroupNorm_0"]["bias"], p["Conv_1"]["kernel"],
                p["GroupNorm_1"]["scale"], p["GroupNorm_1"]["bias"],
                stride=stride, residual=(stride == 1 and in_ch == feats),
                block_b=block_b, interpret=interpret,
            )
            i += 1

    # GDC as multiply+reduce: kernel [h, w, 1, C] applied per channel
    gdc = params["Conv_1"]["kernel"].astype(dtype)
    x = jnp.einsum("nhwc,hwc->nc", x.astype(dtype), gdc[:, :, 0, :])
    dense = params["Dense_0"]
    x = (x.astype(dtype) @ dense["kernel"].astype(dtype)
         + dense["bias"].astype(dtype))
    x = x.astype(jnp.float32)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def arcface_loss(
    embeddings: jnp.ndarray,
    labels: jnp.ndarray,
    weights: jnp.ndarray,
    margin: float = 0.5,
    scale: float = 32.0,
) -> jnp.ndarray:
    """Additive angular margin softmax loss.

    ``weights`` [C, E] are per-class directions (L2-normalized here);
    the true-class logit's angle is widened by ``margin`` before the scaled
    softmax, pushing embeddings toward tight per-class cones.
    """
    w = weights / jnp.maximum(jnp.linalg.norm(weights, axis=-1, keepdims=True), 1e-12)
    cos = jnp.clip(embeddings @ w.T, -1.0 + 1e-6, 1.0 - 1e-6)  # [N, C]
    theta = jnp.arccos(cos)
    onehot = jax.nn.one_hot(labels, w.shape[0], dtype=cos.dtype)
    cos_margin = jnp.cos(theta + margin)
    logits = scale * (onehot * cos_margin + (1.0 - onehot) * cos)
    return optax.softmax_cross_entropy(logits, onehot).mean()


def augment_batch(key: jax.Array, x: jnp.ndarray, *, occlusion_p: float = 0.5,
                  max_shift: int = 3, max_rotate_deg: float = 14.0,
                  scale_jitter: float = 0.1) -> jnp.ndarray:
    """On-device train-time augmentation for STANDARDIZED [N, H, W] faces:
    per-sample horizontal flip, rotation/scale resample, +/-max_shift
    translation (edge-padded dynamic slice), and a mean-fill cutout
    rectangle with probability ``occlusion_p`` — the invariances (pose,
    partial occlusion) a robust verifier needs but a 10-views-per-identity
    enrolment set cannot teach on its own. Pure jnp: runs inside the
    jitted train step."""
    from jax.scipy.ndimage import map_coordinates

    n, h, w = x.shape
    (k_flip, k_oy, k_ox, k_app, k_oh, k_ow, k_cy, k_cx,
     k_rot, k_sc) = jax.random.split(key, 10)
    flip = jax.random.bernoulli(k_flip, 0.5, (n,))
    x = jnp.where(flip[:, None, None], x[:, :, ::-1], x)
    if max_rotate_deg or scale_jitter:
        ang = jax.random.uniform(k_rot, (n,), minval=-max_rotate_deg,
                                 maxval=max_rotate_deg) * (jnp.pi / 180.0)
        sc = jax.random.uniform(k_sc, (n,), minval=1.0 - scale_jitter,
                                maxval=1.0 + scale_jitter)
        cy0, cx0 = (h - 1) / 2.0, (w - 1) / 2.0
        yy, xx = jnp.mgrid[0:h, 0:w]

        def _warp(img, a, s):
            cos_a, sin_a = jnp.cos(a), jnp.sin(a)
            y0 = yy - cy0
            x0 = xx - cx0
            ys = (cos_a * y0 + sin_a * x0) / s + cy0
            xs = (-sin_a * y0 + cos_a * x0) / s + cx0
            return map_coordinates(img, [ys, xs], order=1, mode="nearest")

        x = jax.vmap(_warp)(x, ang, sc)
    pad = max_shift
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)), mode="edge")
    oy = jax.random.randint(k_oy, (n,), 0, 2 * pad + 1)
    ox = jax.random.randint(k_ox, (n,), 0, 2 * pad + 1)
    x = jax.vmap(
        lambda img, a, b: jax.lax.dynamic_slice(img, (a, b), (h, w))
    )(xp, oy, ox)
    apply = jax.random.bernoulli(k_app, occlusion_p, (n,))
    oh = jax.random.randint(k_oh, (n,), h // 5, h // 2)
    ow = jax.random.randint(k_ow, (n,), w // 5, w // 2)
    cy = jax.random.randint(k_cy, (n,), 0, h)
    cx = jax.random.randint(k_cx, (n,), 0, w)
    yy = jnp.arange(h)[None, :, None]
    xx = jnp.arange(w)[None, None, :]
    box = ((yy >= cy[:, None, None]) & (yy < (cy + oh)[:, None, None])
           & (xx >= cx[:, None, None]) & (xx < (cx + ow)[:, None, None]))
    # mean fill (inputs are per-image standardized, so 0 == the mean)
    return jnp.where(box & apply[:, None, None], 0.0, x)


def make_train_step(model: FaceEmbedNet, optimizer, margin: float = 0.5,
                    scale: float = 32.0, augment: bool = False):
    """Returns a jitted (params, opt_state, batch_x, batch_y, key,
    margin_scale) -> updated step; ``augment`` applies ``augment_batch``
    in-graph; ``margin_scale`` (traced f32 in [0, 1]) ramps the angular
    margin so hard distributions don't collapse at cold start."""

    @jax.jit
    def step(params, opt_state, x, y, key, margin_scale):
        if augment:
            x = augment_batch(key, x)

        def loss_fn(p):
            emb = model.apply({"params": p["net"]}, x)
            return arcface_loss(emb, y, p["head"], margin * margin_scale, scale)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def init_embedder(
    model: FaceEmbedNet, num_classes: int, input_shape: Tuple[int, int], seed: int = 0
) -> Dict[str, Any]:
    """Initialize {net, head} params for training."""
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((1, *input_shape), dtype=jnp.float32)
    # jitted: an eager init dispatches (and on an accelerator compiles)
    # every initializer op one by one; the values are bit-identical.
    variables = jax.jit(model.init)(rng, dummy)
    head = jax.random.normal(
        jax.random.fold_in(rng, 1), (num_classes, model.embed_dim), dtype=jnp.float32
    )
    return {"net": variables["params"], "head": head}


def train_embedder(
    model: FaceEmbedNet,
    params: Dict[str, Any],
    images: np.ndarray,
    labels: np.ndarray,
    *,
    steps: int = 200,
    batch_size: int = 64,
    learning_rate: float = 1e-3,
    margin: float = 0.5,
    scale: float = 32.0,
    seed: int = 0,
    augment: bool = False,
    lr_schedule: str = "constant",
    log_every: int = 0,
) -> Dict[str, Any]:
    """Host loop of jitted ArcFace steps over shuffled fixed-size batches.

    ``lr_schedule="cosine"`` decays to lr/100 over ``steps`` — the standard
    recipe once augmentation makes long runs productive."""
    if lr_schedule == "cosine":
        sched = optax.cosine_decay_schedule(learning_rate, steps, alpha=0.01)
        optimizer = optax.adam(sched)
    else:
        optimizer = optax.adam(learning_rate)
    opt_state = optimizer.init(params)
    step = make_train_step(model, optimizer, margin, scale, augment=augment)
    x = jnp.asarray(images, dtype=jnp.float32)
    y = jnp.asarray(labels, dtype=jnp.int32)
    n = x.shape[0]
    batch_size = min(batch_size, n)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    warmup = max(1, int(0.1 * steps))  # margin ramp: 0 -> full over 10%
    for i in range(steps):
        idx = jnp.asarray(rng.choice(n, size=batch_size, replace=n < batch_size))
        key, sub = jax.random.split(key)
        mscale = jnp.float32(min(1.0, i / warmup))
        params, opt_state, loss = step(params, opt_state, x[idx], y[idx],
                                       sub, mscale)
        if log_every and (i + 1) % log_every == 0:
            print(f"  arcface step {i + 1}/{steps}: loss {float(loss):.4f}")
    return params


def normalize_faces(x: jnp.ndarray, size: Tuple[int, int]) -> jnp.ndarray:
    """Serving-path face normalization: resize + per-image standardize."""
    x = image_ops.resize(jnp.asarray(x, jnp.float32), size)
    mean = jnp.mean(x, axis=(-2, -1), keepdims=True)
    std = jnp.maximum(jnp.std(x, axis=(-2, -1), keepdims=True), 1e-6)
    return (x - mean) / std


class CNNEmbedding(AbstractFeature):
    """The CNN embedder behind the ``AbstractFeature`` boundary.

    ``compute(X, y)`` trains (or fine-tunes preloaded params) with ArcFace on
    the enrolled dataset and returns embeddings; ``extract`` embeds new
    faces. Composes with ``NearestNeighbor(CosineDistance())`` into the
    north-star ``PredictableModel``.
    """

    name = "cnn_embedding"
    sample_ndim = 2

    def __init__(
        self,
        embed_dim: int = 128,
        input_size: Tuple[int, int] = (112, 112),
        stem_features: int = 32,
        stage_features: Sequence[int] = (64, 128, 128),
        stage_blocks: Sequence[int] = (2, 2, 2),
        block: str = "separable",
        space_to_depth: int = 1,
        norm: str = "full",
        train_steps: int = 200,
        batch_size: int = 64,
        learning_rate: float = 1e-3,
        seed: int = 0,
        augment: bool = False,
        lr_schedule: str = "constant",
        tta: bool = False,
    ):
        self.embed_dim = int(embed_dim)
        self.input_size = tuple(int(v) for v in input_size)
        self.stem_features = int(stem_features)
        self.stage_features = tuple(int(v) for v in stage_features)
        self.stage_blocks = tuple(int(v) for v in stage_blocks)
        self.block = str(block)
        self.space_to_depth = int(space_to_depth)
        self.norm = str(norm)
        self.train_steps = int(train_steps)
        self.batch_size = int(batch_size)
        self.learning_rate = float(learning_rate)
        self.seed = int(seed)
        self.augment = bool(augment)
        self.lr_schedule = str(lr_schedule)
        self.tta = bool(tta)
        self.net = FaceEmbedNet(
            embed_dim=self.embed_dim,
            stem_features=self.stem_features,
            stage_features=self.stage_features,
            stage_blocks=self.stage_blocks,
            block=self.block,
            space_to_depth=self.space_to_depth,
            norm=self.norm,
        )
        self._params: Optional[Dict[str, Any]] = None
        self._apply = jax.jit(lambda p, x: self.net.apply({"params": p}, x))

    # -- feature protocol --
    def compute(self, X, y):
        if isinstance(X, (list, tuple)):
            X = np.stack([np.asarray(v) for v in X])
        x = np.asarray(normalize_faces(X, self.input_size))
        y = np.asarray(y, dtype=np.int32)
        # Remap to 0-based contiguous indices before sizing the ArcFace
        # head: sparse labels ({5, 900}) must not allocate a 901-row head,
        # and negative labels must not silently produce wrong one-hot rows.
        # (The mapping itself isn't kept: the head is training-only scaffold;
        # prediction goes through the classifier's own label handling.)
        if len(y):
            classes, y = np.unique(y, return_inverse=True)
            y = y.astype(np.int32)
            num_classes = len(classes)
        else:
            num_classes = 1
        params = self._params
        if params is None:
            params = init_embedder(self.net, num_classes, self.input_size, self.seed)
        elif params["head"].shape[0] != num_classes:
            rng = jax.random.PRNGKey(self.seed + 1)
            params = dict(params, head=jax.random.normal(
                rng, (num_classes, self.embed_dim), dtype=jnp.float32))
        if self.train_steps > 0:
            params = train_embedder(
                self.net, params, x, y,
                steps=self.train_steps, batch_size=self.batch_size,
                learning_rate=self.learning_rate, seed=self.seed,
                augment=self.augment, lr_schedule=self.lr_schedule,
            )
        self._params = params
        return self._extract_batch(jnp.asarray(X, jnp.float32))

    def _extract_batch(self, X):
        if self._params is None:
            raise RuntimeError("CNNEmbedding.extract called before compute()")
        x = normalize_faces(X, self.input_size)
        emb = self._apply(self._params["net"], x)
        if self.tta:
            # Flip test-time augmentation (standard verification practice):
            # average the embedding with the mirrored view's, re-normalize.
            emb_f = self._apply(self._params["net"], x[:, :, ::-1])
            emb = emb + emb_f
            emb = emb / jnp.maximum(
                jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-12)
        return emb

    def load_params(self, params: Dict[str, Any]) -> None:
        """Install pretrained {net, head} params (skips/limits training)."""
        self._params = params

    # -- serialization protocol --
    def get_config(self):
        return {
            "embed_dim": self.embed_dim,
            "input_size": list(self.input_size),
            "stem_features": self.stem_features,
            "stage_features": list(self.stage_features),
            "stage_blocks": list(self.stage_blocks),
            "block": self.block,
            "space_to_depth": self.space_to_depth,
            "norm": self.norm,
            "train_steps": self.train_steps,
            "batch_size": self.batch_size,
            "learning_rate": self.learning_rate,
            "seed": self.seed,
            "augment": self.augment,
            "lr_schedule": self.lr_schedule,
            "tta": self.tta,
        }

    @classmethod
    def from_config(cls, config):
        config = dict(config)
        config["input_size"] = tuple(config.get("input_size", (112, 112)))
        config["stage_features"] = tuple(config.get("stage_features", (64, 128, 128)))
        config["stage_blocks"] = tuple(config.get("stage_blocks", (2, 2, 2)))
        config.setdefault("block", "separable")  # pre-r3 checkpoints
        config.setdefault("space_to_depth", 1)  # pre-r4 checkpoints
        config.setdefault("norm", "full")
        config.setdefault("augment", False)
        config.setdefault("lr_schedule", "constant")
        config.setdefault("tta", False)
        return cls(**config)

    def get_state(self):
        if self._params is None:
            return {}
        flat = jax.tree_util.tree_flatten_with_path(self._params["net"])[0]
        state = {"head": np.asarray(self._params["head"])}
        for path, leaf in flat:
            key = "net/" + "/".join(str(getattr(p, "key", p)) for p in path)
            state[key] = np.asarray(leaf)
        return state

    def set_state(self, state):
        if not state:
            return
        net: Dict[str, Any] = {}
        for key, leaf in state.items():
            if key == "head":
                continue
            parts = key.split("/")[1:]
            node = net
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(leaf)
        self._params = {"net": net, "head": jnp.asarray(state["head"])}

"""IResNet face embedder (Flax): the net the field deploys behind an
ArcFace loss (insightface ``arcface_torch/backbones/iresnet.py``; the
``buffalo_l`` pack's ``w600k_r50`` is ``IResNet`` with blocks (3, 4, 14, 3),
112x112 crops in, 512-d rows out), served through the same
``AbstractFeature`` boundary and the same fused step as ``FaceEmbedNet``.

The equations, for x of [N, 112, 112, 3]:

- stem: conv 3x3, 3 -> 64, stride 1, pad 1, no bias -> BatchNorm -> PReLU;
- four stages of widths (64, 128, 256, 512) and (3, 4, 14, 3) blocks; the
  first block of a stage has stride 2 and a shortcut conv 1x1 stride 2, no
  bias -> BatchNorm; spatial 112 -> 56 -> 28 -> 14 -> 7;
- block(x): y = BN1(x); y = conv3x3(y, in -> out, stride 1);
  y = PReLU(BN2(y)); y = conv3x3(y, out -> out, stride s); y = BN3(y);
  out = y + shortcut(x);
- head: BatchNorm(512) -> flatten 7*7*512 = 25,088 -> linear 25,088 -> 512
  with bias -> BatchNorm(512) -> L2 normalise. Dropout is the identity at
  inference.

6.31 G multiply-adds and 43.6 M parameters a face (``multiply_adds``,
``parameter_count``; tests pin both).

Design, TPU-first, and what differs from the published code:

- Inference only. BatchNorm applies its STORED mean and variance
  (eps 1e-5); they live in the ``params`` collection beside scale and bias,
  so the serving step's ``net.apply({"params": p}, x)`` needs no second
  collection. ``calibrate_batch_stats`` is the one way to set them here: a
  forward pass that normalises every BatchNorm by its batch's own moments
  and stores those. ``ocvf-train`` does not learn this net yet.
- Convolutions and the head's matmul take bf16 operands (MXU) with f32
  parameters, as the other nets; the per-channel affine of a BatchNorm and
  the PReLU run in f32 on the conv's output and store bf16 (XLA fuses them
  into the neighbouring conv; nothing is folded into a kernel at load
  time, so a checkpoint holds exactly the published parameters). The head's
  512 outputs, its last BatchNorm and the L2 norm stay f32.
- Layout is NHWC; the flatten before the linear is therefore (H, W, C)
  ordered where the published one is (C, H, W): importing published
  weights means permuting that kernel's rows, nothing else.
- A [N, H, W] grayscale batch (what the serving step crops) is replicated
  onto the stem's ``in_channels`` planes: the published stem width is kept.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from opencv_facerecognizer_tpu.models.embedder import normalize_faces
from opencv_facerecognizer_tpu.models.feature import AbstractFeature

#: the published r50's input (``IResNet``'s defaults are its widths and depths:
#: ``arcface_torch/configs/ms1mv3_r50.py``, network "r50", embedding_size 512)
R50_FACE_SIZE = (112, 112)


class _BatchNorm(nn.Module):
    """Inference-mode BatchNorm over the last axis: the stored moments,
    then scale and bias, as one f32 multiply-add per element. With
    ``calibrate`` the batch's own moments take the stored ones' place and
    are sown into ``batch_stats`` (``calibrate_batch_stats`` reads them)."""

    eps: float = 1e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (c,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (c,), jnp.float32)
        x = x.astype(jnp.float32)
        if self.calibrate:
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x, axis=axes)
            var = jnp.mean(jnp.square(x - mean), axis=axes)
            self.sow("batch_stats", "mean", mean)
            self.sow("batch_stats", "var", var)
        mult = scale * jax.lax.rsqrt(var + self.eps)
        return (x * mult + (bias - mean * mult)).astype(self.dtype)


class _PReLU(nn.Module):
    """max(0, x) + slope * min(0, x), one slope a channel (0.25 at start)."""

    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        slope = self.param("slope", nn.initializers.constant(0.25),
                           (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return jnp.where(x >= 0, x, slope * x).astype(self.dtype)


def _conv(features: int, kernel: int, stride: int, dtype, name: str):
    """The published convolutions: no bias, pad 1 for 3x3, pad 0 for 1x1
    (explicit: flax's "SAME" pads a strided conv on the far side only)."""
    pad = (kernel - 1) // 2
    return nn.Conv(features, (kernel, kernel), strides=(stride, stride),
                   padding=((pad, pad), (pad, pad)), use_bias=False,
                   dtype=dtype, name=name)


class _IBasicBlock(nn.Module):
    features: int
    stride: int = 1
    eps: float = 1e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def bn(name):
            return _BatchNorm(self.eps, self.calibrate, self.dtype, name=name)

        y = bn("bn1")(x)
        y = _conv(self.features, 3, 1, self.dtype, "conv1")(y)
        y = _PReLU(self.dtype, name="prelu")(bn("bn2")(y))
        y = _conv(self.features, 3, self.stride, self.dtype, "conv2")(y)
        y = bn("bn3")(y)
        if self.stride != 1 or x.shape[-1] != self.features:
            x = _conv(self.features, 1, self.stride, self.dtype,
                      "shortcut_conv")(x)
            x = bn("shortcut_bn")(x)
        return y + x


class IResNet(nn.Module):
    """[N, H, W] or [N, H, W, in_channels] standardized crops -> [N, E]
    unit embeddings. The defaults are the published r50; tests use a small
    variant (any H, W divisible by 16)."""

    #: the name of the feature class that owns this net: what the step's
    #: dispatch reports as its embedder (``parallel.pipeline``)
    feature_name = "iresnet_embedding"

    embed_dim: int = 512
    stem_features: int = 64
    stage_features: Sequence[int] = (64, 128, 256, 512)
    stage_blocks: Sequence[int] = (3, 4, 14, 3)
    in_channels: int = 3
    eps: float = 1e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def bn(name, dtype=self.dtype):
            return _BatchNorm(self.eps, self.calibrate, dtype, name=name)

        if x.ndim == 3:
            x = jnp.broadcast_to(x[..., None], (*x.shape, self.in_channels))
        x = x.astype(self.dtype)
        x = _conv(self.stem_features, 3, 1, self.dtype, "stem_conv")(x)
        x = _PReLU(self.dtype, name="stem_prelu")(bn("stem_bn")(x))
        for s, (feats, blocks) in enumerate(zip(self.stage_features,
                                                self.stage_blocks)):
            for b in range(blocks):
                x = _IBasicBlock(feats, 2 if b == 0 else 1, self.eps,
                                 self.calibrate, self.dtype,
                                 name=f"stage{s + 1}_block{b}")(x)
        x = bn("head_bn")(x)
        x = x.reshape((x.shape[0], -1))
        kernel = self.param("fc_kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.embed_dim), jnp.float32)
        bias = self.param("fc_bias", nn.initializers.zeros,
                          (self.embed_dim,), jnp.float32)
        x = jnp.dot(x, kernel.astype(self.dtype),
                    preferred_element_type=jnp.float32) + bias
        x = bn("features_bn", jnp.float32)(x)
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def multiply_adds(net: IResNet, input_size: Tuple[int, int]) -> int:
    """Multiply-adds of one face through every convolution and the linear
    head (6,309,330,944 for the r50 at 112x112; norms and PReLUs are left
    out, as the published count leaves them)."""
    h, w = input_size
    ch = net.stem_features
    total = 9 * net.in_channels * ch * h * w
    for feats, blocks in zip(net.stage_features, net.stage_blocks):
        total += 9 * ch * feats * h * w  # the first block's conv1, before its stride
        h, w = (h - 1) // 2 + 1, (w - 1) // 2 + 1
        total += (9 * feats + ch) * feats * h * w  # its conv2 and its 1x1 shortcut
        total += (blocks - 1) * 2 * 9 * feats * feats * h * w
        ch = feats
    return total + h * w * ch * net.embed_dim


def parameter_count(params: Dict[str, Any]) -> int:
    """Learned parameters: every leaf but the BatchNorms' stored moments
    (buffers in the published code: 43,590,848 for the r50)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return sum(int(np.prod(leaf.shape)) for path, leaf in flat
               if getattr(path[-1], "key", None) not in ("mean", "var"))


def random_params(net: IResNet, input_size: Tuple[int, int],
                  seed: int = 0) -> Dict[str, Any]:
    """Seeded parameters with nothing left at a default a fault could hide
    behind: convolutions and the linear as flax draws them, BatchNorm
    scales in [0.5, 1.5], biases N(0, 0.1), PReLU slopes in [0.1, 0.4].
    The stored moments stay (0, 1) until ``calibrate_batch_stats``."""
    key = jax.random.PRNGKey(int(seed))
    dummy = jnp.zeros((1, *input_size), jnp.float32)
    # jitted: an eager init dispatches every initializer one by one
    params = jax.jit(net.init)(key, dummy)["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    draws = {"scale": lambda k, s: jax.random.uniform(k, s, minval=0.5, maxval=1.5),
             "bias": lambda k, s: 0.1 * jax.random.normal(k, s),
             "slope": lambda k, s: jax.random.uniform(k, s, minval=0.1, maxval=0.4)}
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        draw = draws.get(getattr(path[-1], "key", None))
        leaves.append(leaf if draw is None else
                      draw(jax.random.fold_in(key, i + 1), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def calibrate_batch_stats(net: IResNet, params: Dict[str, Any],
                          x: jnp.ndarray
                          ) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """(``params`` with every BatchNorm's stored mean and variance replaced
    by the moments of its input over the batch ``x`` of standardized crops,
    the embeddings of ``x``). Each layer sees what the layers before it
    give once calibrated: one forward pass in which every BatchNorm
    normalises by its own batch. Inference on ``x`` with the returned
    parameters repeats that pass, so its output is returned with them."""
    probe = net.clone(calibrate=True)
    # ocvf-lint: boundary=jit-recompile-hazard -- one-off calibration pass at checkpoint-making time, never on the serving path
    emb, sown = jax.jit(lambda p, v: probe.apply(
        {"params": p}, v, mutable=["batch_stats"]))(params, x)

    def merge(p, s):
        out = dict(p)
        for name, sub in s.items():
            if name in ("mean", "var"):
                out[name] = sub[0]  # sow keeps a tuple of the values sown
            else:
                out[name] = merge(p[name], sub)
        return out

    return merge(params, sown["batch_stats"]), emb


class SeededNetFeature(AbstractFeature):
    """What the embedders that are not trained here share behind the
    ``AbstractFeature`` boundary, beside ``CNNEmbedding``: the attributes
    the serving app reads (``net``, ``input_size``, ``_params["net"]``)
    and the checkpoint protocol. A subclass builds ``self.net`` (a flax
    module with a ``calibrate`` field whose BatchNorms are ``_BatchNorm``),
    then calls ``_bind``; it states ``get_config`` and how its parameters
    are drawn (``_random_params``).

    ``compute(X, y)`` does not train: with no parameters loaded it draws
    them from ``seed`` and then, either way, fits the BatchNorms' stored
    moments to ``X`` (``calibrate_batch_stats``); ``extract`` embeds.
    Learned weights arrive through ``set_state``."""

    sample_ndim = 2

    def _bind(self, net, input_size: Tuple[int, int], seed: int) -> None:
        self.net = net
        self.input_size = tuple(int(v) for v in input_size)
        self.seed = int(seed)
        self._params: Optional[Dict[str, Any]] = None
        # closes over the net, not over self: a jitted function that held the
        # feature would make a cycle of it, and its parameters (455 MB for
        # ViT-B) would stay on the device until the cycle collector ran
        self._apply = jax.jit(lambda p, x: net.apply({"params": p}, x))

    def _random_params(self) -> Dict[str, Any]:
        raise NotImplementedError

    # -- feature protocol --
    def compute(self, X, y=None):
        if isinstance(X, (list, tuple)):
            X = np.stack([np.asarray(v) for v in X])
        X = jnp.asarray(X, jnp.float32)
        net_params = (self._params["net"] if self._params is not None else
                      self._random_params())
        net_params, emb = calibrate_batch_stats(
            self.net, net_params, normalize_faces(X, self.input_size))
        self._params = {"net": net_params}
        return emb

    def _extract_batch(self, X):
        if self._params is None:
            raise RuntimeError(f"{type(self).__name__}.extract called before "
                               "compute() or set_state()")
        return self._apply(self._params["net"],
                           normalize_faces(X, self.input_size))

    # -- serialization protocol --
    def get_state(self):
        if self._params is None:
            return {}
        flat = jax.tree_util.tree_flatten_with_path(self._params["net"])[0]
        return {"net/" + "/".join(str(p.key) for p in path): np.asarray(leaf)
                for path, leaf in flat}

    def set_state(self, state):
        if not state:
            return
        net: Dict[str, Any] = {}
        for key, leaf in state.items():
            node = net
            *parents, last = key.split("/")[1:]
            for part in parents:
                node = node.setdefault(part, {})
            node[last] = jnp.asarray(leaf)
        self._params = {"net": net}


class IResNetEmbedding(SeededNetFeature):
    """An ``IResNet`` behind the ``AbstractFeature`` boundary
    (``SeededNetFeature``: seeded parameters, calibrated BatchNorms)."""

    name = IResNet.feature_name

    def __init__(
        self,
        embed_dim: int = 512,
        input_size: Tuple[int, int] = R50_FACE_SIZE,
        stem_features: int = 64,
        stage_features: Sequence[int] = (64, 128, 256, 512),
        stage_blocks: Sequence[int] = (3, 4, 14, 3),
        in_channels: int = 3,
        eps: float = 1e-5,
        seed: int = 0,
    ):
        self.embed_dim = int(embed_dim)
        self.stem_features = int(stem_features)
        self.stage_features = tuple(int(v) for v in stage_features)
        self.stage_blocks = tuple(int(v) for v in stage_blocks)
        self.in_channels = int(in_channels)
        self.eps = float(eps)
        self._bind(IResNet(
            embed_dim=self.embed_dim, stem_features=self.stem_features,
            stage_features=self.stage_features, stage_blocks=self.stage_blocks,
            in_channels=self.in_channels, eps=self.eps), input_size, seed)

    def _random_params(self):
        return random_params(self.net, self.input_size, self.seed)

    def get_config(self):
        return {
            "embed_dim": self.embed_dim,
            "input_size": list(self.input_size),
            "stem_features": self.stem_features,
            "stage_features": list(self.stage_features),
            "stage_blocks": list(self.stage_blocks),
            "in_channels": self.in_channels,
            "eps": self.eps,
            "seed": self.seed,
        }

"""Vision-transformer face embedder (Flax): the other family of the public
model zoo (insightface ``arcface_torch/backbones/vit.py``; ``vit_b`` of
``configs/wf42m_pfc03_40epoch_8gpu_vit_b.py`` is ``VisionTransformer(
img_size=112, patch_size=9, num_classes=512, embed_dim=512, depth=24,
num_heads=8)``, the "ViT-B-11G" row of that README), served through the same
``AbstractFeature`` boundary and the same fused step as ``IResNet``.

The equations at inference (drop path, dropout and token masking are
training-only), for x of [N, 112, 112, 3]:

- patch embedding: convolution 9x9, stride 9, no padding, 3 -> 512 with
  bias: 12 x 12 = 144 tokens (112 = 12 * 9 + 4: the last 4 rows and columns
  are never read); x = patches + pos_embed ([144, 512], learned). No class
  token;
- 24 pre-norm blocks: x = x + proj(Attn(LN1(x))); x = x + fc2(ReLU6(fc1(
  LN2(x)))). qkv = Linear(512 -> 1536, no bias) split into 8 heads of 64;
  A = softmax(q k^T / sqrt(64)) over the 144 keys; Attn = A v, heads
  concatenated; proj = Linear(512 -> 512, bias); fc1 = Linear(512 -> 2048,
  bias), ReLU6 (not GELU), fc2 = Linear(2048 -> 512, bias); LayerNorm eps
  1e-5;
- final LayerNorm, the 144 tokens flattened token-major to 73,728, then
  Linear(73,728 -> 512, no bias) -> BatchNorm(eps 2e-5) -> Linear(512 -> 512,
  no bias) -> BatchNorm(eps 2e-5); L2 norm for the cosine match.

11.44 G multiply-adds and 113.83 M parameters a face (``multiply_adds``,
``parameter_count``; tests pin both, and the published 1.5 / 5.7 / 25.3 G of
ViT-T / -S / -L by the same count).

Design, TPU-first, and what differs from the published code:

- Every matmul (patch, qkv, q k^T, A v, proj, both MLP layers, the head's
  two) takes bf16 operands and accumulates in f32 on the MXU; parameters
  are f32. Softmax and both kinds of norm run in f32. The residual stream
  is stored bf16: a branch's f32 output is added to the stream in f32 and
  the sum rounded once. The head's 512 outputs, its last BatchNorm and the
  L2 norm stay f32. (The published module leaves autocast for q k^T,
  softmax and A v: float32 operands there.)
- What the chip's time asked for (PERF.md, PRs 46 and 47): 1 / sqrt(head)
  is folded into q's columns of the stored qkv kernel (exact for the
  published head of 64); the softmax's division is put off to A v's
  [T, head] output (exp(s - max) rounded to bf16 as A v's operand, summed in
  f32), which changes A's rounding and nothing else. From the block's
  normalised input to ``proj``'s input the attention is
  ``ops.vit_attention.attend``: one set of equations with two lowerings,
  chosen from what the computation is LOWERED for and the shapes alone (no
  flag, no variable). Lowered for one TPU chip, where ``head`` divides 128,
  the width is a multiple of 128, the crops a multiple of 8 (the serving
  rungs' 64 / 256 / 1,024 slots, enrolment's 32) and a crop at most 256
  tokens, ONE Pallas kernel a block cuts q, k and v out of the one
  [N, T, 3 d] qkv result and never writes a score to HBM (1.17 ms a block
  at 1,024 faces); everywhere else (the CPU, ViT-L's head of 96, a ragged
  N, a longer sequence, a ``jit`` that XLA partitions over several chips,
  under ``vmap`` or ``grad``) XLA's form: q, k and v written head-major by
  three matmuls over the one stored kernel and the [N, heads, T, T] float32
  scores in memory (8.4 ms a block on the chip). The text lowered for a CPU
  is XLA's form letter for letter.
- The blocks are UNROLLED, each with parameters of its own (``block0`` ..):
  a checkpoint holds exactly the published parameters block by block, XLA
  fuses across a block's boundary, and the profiler names every block.
  Scanned blocks would compile a rung faster and stack 24 blocks' weights
  into arrays no published file has.
- Inference only, as ``IResNet``: the head's BatchNorms apply STORED
  moments kept in ``params`` (``iresnet._BatchNorm``), set by one
  calibration pass (``iresnet.calibrate_batch_stats``).
- A patch is flattened (kh, kw, C) where the published convolution's
  kernel is (C, kh, kw): a permutation of that kernel's rows. The flatten
  before the head is token-major as published.
- ``jax.named_scope``s for the trace, inside whatever scope the caller
  opens: ``vit_attn`` round q k^T, softmax and A v only (in either form of
  the attention: not the qkv matmul, not ``proj``), ``vit_mlp`` round
  fc1 .. fc2, ``vit_head`` round the flatten and the head.
- A [N, H, W] grayscale batch (what the serving step crops) is replicated
  onto the patch embedding's ``in_channels`` planes.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from opencv_facerecognizer_tpu.models.iresnet import (
    SeededNetFeature, _BatchNorm, parameter_count)
from opencv_facerecognizer_tpu.ops import vit_attention

#: the published vit_b's input
VIT_B_FACE_SIZE = (112, 112)
#: the published initialiser's deviation (trunc_normal_(std=.02))
PUBLISHED_INIT_STD = 0.02


# the published trunc_normal_(std=.02) cuts at +-2 ABSOLUTE, a hundred
# deviations out: a plain normal (``random_params`` draws at another scale)
_INIT = nn.initializers.normal(PUBLISHED_INIT_STD)


class _Linear(nn.Module):
    """x @ kernel (+ bias): operands in ``dtype``, accumulation and the
    result in f32."""

    features: int
    use_bias: bool = True
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.features),
                            jnp.float32)
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,), jnp.float32)
        return y


class _QKV(nn.Module):
    """The published ``qkv = Linear(d -> 3 d, no bias)`` as it is stored
    (one ``kernel`` of [d, 3 d], columns ordered (q | k | v) x heads x
    head), handed to ``ops.vit_attention.attend`` as [d, 3, heads, head] in
    the operands' dtype: each form of the attention applies it in the
    layout its matmuls batch over (XLA's as three matmuls that write q, k
    and v head-major, so nothing is transposed between them; the kernel's as
    the one matmul whose [N, T, 3 d] result it cuts by lanes). 1 / sqrt(head)
    multiplies q's columns of the kernel: for the published head of 64 a
    power of two, so q k^T is bit for bit that of scaling the scores."""

    heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, width: int):
        head = width // self.heads
        kernel = self.param("kernel", _INIT, (width, 3 * width), jnp.float32)
        kernel = kernel.reshape(width, 3, self.heads, head)
        scale = jnp.asarray([head ** -0.5, 1.0, 1.0], jnp.float32)
        return (kernel * scale[None, :, None, None]).astype(self.dtype)


def _layer_norm(eps: float, name: str) -> nn.LayerNorm:
    """f32 whatever it is handed (the two-pass variance, as the plain
    reference writes it)."""
    return nn.LayerNorm(epsilon=eps, dtype=jnp.float32, use_fast_variance=False,
                        name=name)


class _Block(nn.Module):
    heads: int
    mlp_ratio: int = 4
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]

        def linear(features, name):
            return _Linear(features, dtype=self.dtype, name=name)

        y = _layer_norm(self.eps, "norm1")(x)
        kernel = _QKV(self.heads, self.dtype, name="qkv")(width)
        # qkv, then q k^T, softmax and A v under the scope ``vit_attn``: one
        # Pallas kernel where the lowering targets a TPU and the shapes fit
        # it, XLA's batched matmuls elsewhere
        y = vit_attention.attend(y.astype(self.dtype), kernel)
        y = linear(width, "proj")(y)
        x = (x.astype(jnp.float32) + y).astype(self.dtype)

        y = _layer_norm(self.eps, "norm2")(x)
        with jax.named_scope("vit_mlp"):
            y = linear(self.mlp_ratio * width, "fc1")(y)
            y = linear(width, "fc2")(jnp.clip(y, 0.0, 6.0))  # ReLU6
        return (x.astype(jnp.float32) + y).astype(self.dtype)


class ViT(nn.Module):
    """[N, H, W] or [N, H, W, in_channels] standardized crops -> [N, out_dim]
    unit embeddings. The defaults are the published vit_b; tests use a
    small variant."""

    #: the name of the feature class that owns this net: what the step's
    #: dispatch reports as its embedder (``parallel.pipeline``)
    feature_name = "vit_embedding"
    #: the custom call every block's attention lowers to where the kernel is
    #: on the path: the dispatch looks for it in the step's lowered text
    attention_kernel = vit_attention.NAME

    embed_dim: int = 512
    depth: int = 24
    heads: int = 8
    patch: int = 9
    mlp_ratio: int = 4
    out_dim: int = 512
    in_channels: int = 3
    layer_norm_eps: float = 1e-5
    head_bn_eps: float = 2e-5
    calibrate: bool = False
    dtype: Any = jnp.bfloat16

    @nn.nowrap
    def tokens(self, input_size: Tuple[int, int]) -> int:
        """Tokens one crop of ``input_size`` becomes."""
        return (input_size[0] // self.patch) * (input_size[1] // self.patch)

    @nn.compact
    def __call__(self, x):
        def linear(features, name, use_bias=True):
            return _Linear(features, use_bias, self.dtype, name=name)

        if x.ndim == 3:
            x = jnp.broadcast_to(x[..., None], (*x.shape, self.in_channels))
        n, h, w, c = x.shape
        p, gh, gw = self.patch, h // self.patch, w // self.patch
        # a stride-p, pad-0 convolution never reads past the last whole patch
        x = x[:, :gh * p, :gw * p].reshape(n, gh, p, gw, p, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, gh * gw, p * p * c)
        x = linear(self.embed_dim, "patch_embed")(x)
        pos = self.param("pos_embed", _INIT, (gh * gw, self.embed_dim),
                         jnp.float32)
        x = (x + pos).astype(self.dtype)
        for i in range(self.depth):
            x = _Block(self.heads, self.mlp_ratio, self.layer_norm_eps,
                       self.dtype, name=f"block{i}")(x)
        x = _layer_norm(self.layer_norm_eps, "norm")(x)
        with jax.named_scope("vit_head"):
            x = linear(self.out_dim, "feature_fc1", use_bias=False)(
                x.reshape(n, -1))
            x = _BatchNorm(self.head_bn_eps, self.calibrate, self.dtype,
                           name="feature_bn1")(x)
            x = linear(self.out_dim, "feature_fc2", use_bias=False)(x)
            x = _BatchNorm(self.head_bn_eps, self.calibrate, jnp.float32,
                           name="feature_bn2")(x)
            return x / jnp.maximum(
                jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def multiply_adds(net: ViT, input_size: Tuple[int, int]) -> int:
    """Multiply-adds of one face through every matmul (11,437,170,688 for
    vit_b at 112x112; norms, softmax and the adds are left out, as the
    published count leaves them)."""
    t, d = net.tokens(input_size), net.embed_dim
    block = (t * d * 3 * d          # qkv
             + 2 * t * t * d        # q k^T and A v, all heads
             + t * d * d            # proj
             + 2 * t * d * net.mlp_ratio * d)
    head = t * d * net.out_dim + net.out_dim * net.out_dim
    return (t * net.patch * net.patch * net.in_channels * d
            + net.depth * block + head)


def parameter_breakdown(params: Dict[str, Any]) -> Dict[str, int]:
    """Learned parameters by part (``iresnet.parameter_count``: the
    BatchNorms' stored moments are buffers and do not count). For vit_b:
    patch 124,928, positions 73,728, blocks 24 x 3,150,848, the last
    LayerNorm 1,024, head 38,012,928: 113,832,960."""
    named = {"patch_embed": "patch", "pos_embed": "positions", "norm": "norm"}
    parts = {"patch": 0, "positions": 0, "blocks": 0, "norm": 0, "head": 0}
    for name, sub in params.items():
        part = "blocks" if name.startswith("block") else named.get(name, "head")
        parts[part] += parameter_count({name: sub})
    return parts


def random_params(net: ViT, input_size: Tuple[int, int], seed: int = 0,
                  init_std: float = PUBLISHED_INIT_STD) -> Dict[str, Any]:
    """Seeded parameters: every Linear's kernel normal with deviation
    ``init_std`` and the positions with 0.02, as published; what the
    published initialiser leaves at a default a fault could hide behind (a
    bias at 0, a norm at (1, 0)) is drawn as a learned file would hold it:
    biases N(0, 0.02), norm scales in [0.5, 1.5], norm biases N(0, 0.1). The
    BatchNorms' stored moments stay (0, 1) until ``calibrate_batch_stats``."""
    key = jax.random.PRNGKey(int(seed))
    dummy = jnp.zeros((1, *input_size), jnp.float32)
    # jitted: an eager init dispatches every initializer one by one
    params = jax.jit(net.init)(key, dummy)["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def draw(path):
        """How the leaf at ``path`` is drawn again, or None: as initialised."""
        if path[-1].key == "kernel":
            return lambda k, s: init_std * jax.random.normal(k, s)
        if path[-1].key == "scale":
            return lambda k, s: jax.random.uniform(k, s, minval=0.5, maxval=1.5)
        if path[-1].key == "bias":
            of_norm = path[-2].key.startswith("norm") or "_bn" in path[-2].key
            return lambda k, s: (0.1 if of_norm else 0.02) * jax.random.normal(k, s)
        return None

    leaves = []
    for i, (path, leaf) in enumerate(flat):
        again = draw(path)
        leaves.append(leaf if again is None else
                      again(jax.random.fold_in(key, i + 1), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


class ViTEmbedding(SeededNetFeature):
    """A ``ViT`` behind the ``AbstractFeature`` boundary
    (``SeededNetFeature``: seeded parameters, calibrated BatchNorms)."""

    name = ViT.feature_name

    def __init__(
        self,
        embed_dim: int = 512,
        input_size: Tuple[int, int] = VIT_B_FACE_SIZE,
        depth: int = 24,
        heads: int = 8,
        patch: int = 9,
        mlp_ratio: int = 4,
        out_dim: int = 512,
        in_channels: int = 3,
        layer_norm_eps: float = 1e-5,
        head_bn_eps: float = 2e-5,
        init_std: float = PUBLISHED_INIT_STD,
        seed: int = 0,
    ):
        self.embed_dim = int(embed_dim)
        self.depth = int(depth)
        self.heads = int(heads)
        self.patch = int(patch)
        self.mlp_ratio = int(mlp_ratio)
        self.out_dim = int(out_dim)
        self.in_channels = int(in_channels)
        self.layer_norm_eps = float(layer_norm_eps)
        self.head_bn_eps = float(head_bn_eps)
        self.init_std = float(init_std)
        if self.embed_dim % self.heads:
            raise ValueError(f"embed_dim {self.embed_dim} does not divide "
                             f"into {self.heads} heads")
        self._bind(ViT(
            embed_dim=self.embed_dim, depth=self.depth, heads=self.heads,
            patch=self.patch, mlp_ratio=self.mlp_ratio, out_dim=self.out_dim,
            in_channels=self.in_channels, layer_norm_eps=self.layer_norm_eps,
            head_bn_eps=self.head_bn_eps), input_size, seed)

    def _random_params(self):
        return random_params(self.net, self.input_size, self.seed, self.init_std)

    def get_config(self):
        return {
            "embed_dim": self.embed_dim,
            "input_size": list(self.input_size),
            "depth": self.depth,
            "heads": self.heads,
            "patch": self.patch,
            "mlp_ratio": self.mlp_ratio,
            "out_dim": self.out_dim,
            "in_channels": self.in_channels,
            "layer_norm_eps": self.layer_norm_eps,
            "head_bn_eps": self.head_bn_eps,
            "init_std": self.init_std,
            "seed": self.seed,
        }

"""One transformer block's attention, from the block's normalised input and
its stored qkv kernel to what ``proj`` takes: one set of equations, two
lowerings of it, chosen from the lowering's platform and the shapes alone.

The equations (``models.vit``): qkv = x @ kernel, split into ``heads`` heads
of ``head`` lanes; per head A = softmax(q k^T) over the keys (1 / sqrt(head)
already multiplies q's columns of the kernel) and out = A v; heads
concatenated. bf16 operands and f32 accumulation in every matmul, the
softmax in f32 with its division put off to A v's [T, head] result:
exp(s - max) is rounded to bf16 as A v's operand, summed in f32, and A v is
divided by the sum.

* **XLA's form** (``_attend_xla``; every platform but the TPU, and every
  shape the kernel does not take): q, k and v written head-major by three
  matmuls, q k^T and A v as matmuls batched over (sequence, head), the
  [N, heads, T, T] f32 scores in memory between them. On the chip that is
  8,192 matmuls a block half an MXU tile deep, 0.68 GB of scores written and
  read and a softmax on rows of 144 padded to 256 lanes: 8.4 ms a block at
  1,024 faces, and 3.0 more outside the scope, in qkv's three layouts and
  the copy in front of ``proj`` (PERF.md, PRs 46 and 47).
* **The kernel** (``attention``; a TPU, ``head`` dividing 128, the width a
  multiple of 128, N a multiple of ``SEQUENCES``, at most ``MAX_TOKENS``
  tokens): ONE Pallas call a block that never writes a score to HBM. Grid
  over (blocks of ``SEQUENCES`` sequences, 128-lane groups of the width);
  q, k and v are cut out of the one [N, T, 3 W] qkv result by three
  BlockSpecs of the same array. Heads are NOT sliced out of the lanes: k and
  v are masked to one head's lanes at a time, so q k_h^T contracts over all
  128 lanes adding exact zeros and p v_h lands in that head's lanes of a
  [T, 128] result. Scores, max, exp and sum stay in VMEM in f32. The output
  is written in the [N, T, W] layout ``proj`` takes. 1.17 ms a block at
  1,024 faces (PERF.md, PR 47).

``attend`` is what ``models.vit._Block`` calls. Where the shapes fit the
kernel (``fits``) it binds a primitive whose lowering rule for the TPU is the
kernel, if the computation is lowered for one chip (or inside a ``shard_map``
over its whole mesh: Mosaic kernels cannot be partitioned by XLA), and whose
rule for everything else is XLA's form, inline: the choice follows what a
computation is LOWERED for (an ahead-of-time compile for a v5e from a CPU
host reaches the kernel; ``jax.default_backend()`` would not say), no flag
or variable enters it, and the text lowered for a CPU is XLA's form letter
for letter. Under ``vmap`` and ``grad`` the primitive is XLA's form. Which
form a step lowered to is read from its lowered text, where ``NAME`` stands
or does not (``parallel.pipeline``, by ``ViT.attention_kernel``). Both forms name the scope ``vit_attn``
round q k^T, softmax and A v, and not round the qkv matmul: the trace
reader's layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import ad, batching, mlir

_LANES = 128  # lanes of a q, k, v or output block: whole heads of a lane group
#: sequences a grid step holds: at 1,024 faces 4 / 8 / 16 read 1.37 / 1.28 /
#: 1.23 ms a block, 32 exhausts VMEM (PERF.md, PR 47); the enrolment graph's
#: 32 crops and every serving rung's slots are multiples of 8
SEQUENCES = 8
#: the longest sequence offered to the kernel: a grid step holds SEQUENCES x
#: T x T scores in f32, their exp and its bf16 copy. Compiled for a v5e from a
#: CPU host, Mosaic takes T up to 384 inside the default 16 MiB of scoped VMEM
#: and refuses 392 (21.5 MiB asked for; PERF.md, PR 47): two lane tiles of
#: keys leave room. Timed on the chip at 144 only.
MAX_TOKENS = 256
#: the custom call's name: what the profiler's operation, and a step's lowered
#: text, hold where the kernel is on the path
NAME = "vit_attention"


def fits(n: int, tokens: int, width: int, heads: int) -> bool:
    """Whether the kernel takes [n, tokens, width] in ``heads`` heads."""
    head = width // heads
    return (width % _LANES == 0 and width % heads == 0
            and _LANES % head == 0 and n % SEQUENCES == 0
            and tokens <= MAX_TOKENS)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, head: int):
    """One (block of sequences, lane group) grid step: blocks [B, T, 128]
    bf16, the lane group's 128 // head heads one after another."""
    q = q_ref[...]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    out = jnp.zeros(q.shape, jnp.float32)
    for h in range(_LANES // head):
        mine = (lane >= h * head) & (lane < (h + 1) * head)
        kh = jnp.where(mine, k, 0.0).astype(q.dtype)
        vh = jnp.where(mine, v, 0.0).astype(q.dtype)
        s = jnp.einsum("bqd,bkd->bqk", q, kh, preferred_element_type=jnp.float32)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        # zero outside this head's lanes: the heads' results add up to the
        # lane group's, each divided by its own sum
        out = out + jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), vh,
                               preferred_element_type=jnp.float32
                               ) / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = out.astype(o_ref.dtype)


def attention(qkv, heads: int, *, interpret: bool = False):
    """[N, T, 3 W] (q | k | v, each heads x head, q scaled) -> [N, T, W] in
    qkv's dtype, for shapes that ``fits`` takes. ``interpret`` runs the
    kernel on the CPU (the tests)."""
    n, t, w3 = qkv.shape
    width = w3 // 3
    if not fits(n, t, width, heads):
        raise ValueError(f"the attention kernel does not take {qkv.shape} in "
                         f"{heads} heads: ask fits() first")
    groups = width // _LANES

    def part(i):
        return pl.BlockSpec((SEQUENCES, t, _LANES),
                            lambda a, b: (a, 0, i * groups + b))

    return pl.pallas_call(
        functools.partial(_kernel, head=width // heads),
        grid=(n // SEQUENCES, groups),
        in_specs=[part(0), part(1), part(2)],
        out_specs=pl.BlockSpec((SEQUENCES, t, _LANES), lambda a, b: (a, 0, b)),
        out_shape=jax.ShapeDtypeStruct((n, t, width), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=NAME,
        interpret=interpret,
    )(qkv, qkv, qkv)


def _attend_xla(x, kernel):
    n, t, width = x.shape
    q, k, v = (jnp.einsum("ntc,chd->nhtd", x, kernel[:, part],
                          preferred_element_type=jnp.float32).astype(x.dtype)
               for part in range(3))
    with jax.named_scope("vit_attn"):
        scores = jnp.einsum("nhqd,nhkd->nhqk", q, k,
                            preferred_element_type=jnp.float32)
        # softmax in f32, its division put off to the [T, head] output:
        # exp(s - max) is rounded to the operands' precision, summed in
        # f32, and A v is divided by the sum (a 1 in every row of A v's
        # operand: the largest weight is exact)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        total = jnp.sum(weights, axis=-1, keepdims=True)
        y = jnp.einsum("nhqk,nhkd->nhqd", weights.astype(x.dtype), v,
                       preferred_element_type=jnp.float32) / total
    return y.transpose(0, 2, 1, 3).reshape(n, t, width).astype(x.dtype)


def _attend_kernel(x, kernel):
    width = x.shape[-1]
    qkv = jnp.dot(x, kernel.reshape(width, 3 * width),
                  preferred_element_type=jnp.float32).astype(x.dtype)
    with jax.named_scope("vit_attn"):
        return attention(qkv, kernel.shape[2])


def _lower_for_tpu(ctx, x, kernel):
    """The kernel where the computation is lowered for ONE chip, or inside a
    ``shard_map`` over every axis of its mesh (what jax's Mosaic lowering
    itself asks: ``tpu_custom_call``); under a ``jit`` that XLA partitions
    over several chips XLA's form, which it can partition."""
    axes = ctx.module_context.axis_context
    if hasattr(axes, "num_devices"):
        whole = axes.num_devices == 1
    else:
        whole = (hasattr(axes, "manual_axes") and
                 frozenset(axes.manual_axes) == frozenset(axes.mesh.axis_names))
    return mlir.lower_fun(_attend_kernel if whole else _attend_xla,
                          multiple_results=False)(ctx, x, kernel)


# The choice is made where a computation is lowered, by what only the lowering
# knows: a primitive with a rule for the TPU and a default one. (Of these
# ``jax.lax.platform_dependent`` follows the platform alone, and leaves a
# ``case`` over a constant in every platform's text.) To ``vmap`` and to
# ``grad`` the primitive is XLA's form: the kernel is the plain forward pass's.
_attend_p = jex_core.Primitive("vit_attend")
_attend_p.def_impl(jax.jit(_attend_p.bind))  # called outside any jit
_attend_p.def_abstract_eval(lambda x, kernel: x.update())
mlir.register_lowering(_attend_p, mlir.lower_fun(_attend_xla, multiple_results=False))
mlir.register_lowering(_attend_p, _lower_for_tpu, platform="tpu")
batching.primitive_batchers[_attend_p] = lambda args, dims: (
    jax.vmap(_attend_xla, in_axes=dims)(*args), 0)
ad.primitive_jvps[_attend_p] = lambda primals, tangents: jax.jvp(
    _attend_xla, primals, tuple(ad.instantiate_zeros(t) for t in tangents))


def attend(x, kernel):
    """x [N, T, W] and the block's qkv kernel as [W, 3, heads, head] (q's
    columns scaled by 1 / sqrt(head)), both in the matmuls' operand dtype
    -> the heads' concatenated A v, [N, T, W] in the same dtype."""
    n, t, width = x.shape
    if fits(n, t, width, kernel.shape[2]):
        return _attend_p.bind(x, kernel)
    return _attend_xla(x, kernel)

"""``ops.vit_attention``: the Pallas kernel (interpret mode, on the CPU)
against a plain float32 head-by-head loop and against XLA's form of the same
equations, what it reads and what it cannot overflow, and the one predicate
that chooses between the two forms. ``tests/test_pallas_match.py`` compiles
the kernel for the chip; the chip runs it (PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.ops import vit_attention

TOKENS = 144  # the published ViT-B's 12 x 12 patches
#: one rounding of a bf16 value: 8 bits of mantissa
BF16_ULP = 2.0 ** -8


def _inputs(n, width, heads, seed, tokens=TOKENS, spread=1.0):
    """x and the block's kernel as ``attend`` takes them, and the one
    [N, T, 3 W] bf16 qkv result the kernel cuts."""
    rng = np.random.default_rng(seed)
    head = width // heads
    x = jnp.asarray(rng.normal(size=(n, tokens, width)), jnp.bfloat16)
    kernel = rng.normal(size=(width, 3, heads, head)) * spread / np.sqrt(width)
    kernel[:, 0] *= head ** -0.5
    kernel = jnp.asarray(kernel, jnp.bfloat16)
    qkv = jnp.dot(x, kernel.reshape(width, 3 * width),
                  preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    return x, kernel, qkv


def _loop(qkv, heads):
    """Float32, head by head, the softmax as the textbook writes it."""
    qkv = np.asarray(qkv, np.float32)
    n, t, w3 = qkv.shape
    width = w3 // 3
    head = width // heads
    out = np.zeros((n, t, width), np.float32)
    for i in range(n):
        for h in range(heads):
            at = slice(h * head, (h + 1) * head)
            q, k, v = (qkv[i, :, part * width:(part + 1) * width][:, at]
                       for part in range(3))
            s = q @ k.T
            a = np.exp(s - s.max(axis=-1, keepdims=True))
            out[i, :, at] = (a / a.sum(axis=-1, keepdims=True)) @ v
    return out


@pytest.mark.parametrize("n,width,heads", [
    (8, 512, 8), (32, 512, 8),      # the published shape: 8 heads of 64
    (8, 128, 4), (8, 128, 2),       # one lane group: heads of 32 and of 64
    (16, 256, 8), (8, 256, 4),      # two lane groups
    (8, 256, 2)])                   # a head that is the whole lane group
def test_kernel_agrees_with_a_float32_head_by_head_loop(n, width, heads):
    """What separates them: exp(s - max) rounded to bf16 as A v's operand
    and the bf16 output, a rounding each."""
    _x, _kernel, qkv = _inputs(n, width, heads, seed=n + width + heads, spread=4.0)
    got = np.asarray(vit_attention.attention(qkv, heads, interpret=True), np.float32)
    want = _loop(qkv, heads)
    assert got.shape == (n, TOKENS, width)
    np.testing.assert_allclose(got, want, atol=3 * BF16_ULP * np.abs(want).max())
    # and not by a scale: the mean error is a fraction of a rounding
    assert np.abs(got - want).mean() < BF16_ULP * np.abs(want).max() / 4


@pytest.mark.parametrize("n,width,heads", [(8, 512, 8), (8, 128, 4), (8, 256, 4)])
def test_kernel_agrees_with_xla_s_form_on_the_same_qkv(n, width, heads):
    """Same operands, same roundings, another order of accumulation: the
    difference is of the order of one rounding of the bf16 output."""
    x, kernel, qkv = _inputs(n, width, heads, seed=3, spread=4.0)
    ours = np.asarray(vit_attention.attention(qkv, heads, interpret=True), np.float32)
    theirs = np.asarray(vit_attention._attend_xla(x, kernel), np.float32)
    np.testing.assert_allclose(ours, theirs, atol=2 * BF16_ULP * np.abs(theirs).max())


def test_scores_over_a_hundred_apart_neither_overflow_nor_vanish():
    """The max is subtracted before exp: a row whose scores lie further
    apart than exp's float32 range (e^88) gives its best key's v, not
    inf / inf."""
    n, width, heads = 8, 128, 2
    qkv = np.zeros((n, TOKENS, 3 * width), np.float32)
    qkv[..., :width] = 4.0                               # every q
    k = np.linspace(-32.0, 32.0, TOKENS)                 # scores -8,192 .. 8,192 a head
    qkv[..., width:2 * width] = k[None, :, None]
    v = np.random.default_rng(5).normal(size=(n, TOKENS, width))
    qkv[..., 2 * width:] = v
    qkv = jnp.asarray(qkv, jnp.bfloat16)
    got = np.asarray(vit_attention.attention(qkv, heads, interpret=True), np.float32)
    assert np.isfinite(got).all()
    scores = 4.0 * 64 * np.asarray(jnp.asarray(k, jnp.bfloat16), np.float32)
    assert scores.max() - np.sort(scores)[-2] > 80
    # all the weight on the last key: every query's output is its v
    want = np.asarray(qkv, np.float32)[:, -1:, 2 * width:]
    np.testing.assert_array_equal(got, np.broadcast_to(want, got.shape))


@pytest.mark.parametrize("width,heads,head_read", [(512, 8, 5), (128, 4, 0), (256, 4, 3)])
def test_a_head_reads_its_own_lanes_only(width, heads, head_read):
    """Every OTHER head's k and v perturbed (their q too): this head's
    output does not change by a bit, and theirs does."""
    _x, _kernel, qkv = _inputs(8, width, heads, seed=7, spread=4.0)
    head = width // heads
    mine = np.zeros(3 * width, bool)
    for part in range(3):
        lo = part * width + head_read * head
        mine[lo:lo + head] = True
    noise = np.random.default_rng(9).normal(size=qkv.shape)
    other = jnp.where(mine, qkv, (qkv.astype(jnp.float32) + noise).astype(qkv.dtype))
    before = np.asarray(vit_attention.attention(qkv, heads, interpret=True), np.float32)
    after = np.asarray(vit_attention.attention(other, heads, interpret=True), np.float32)
    at = slice(head_read * head, (head_read + 1) * head)
    np.testing.assert_array_equal(before[..., at], after[..., at])
    rest = np.ones(width, bool)
    rest[at] = False
    assert np.abs(before[..., rest] - after[..., rest]).max() > 0.1


@pytest.mark.parametrize("n,tokens,width,heads,fits", [
    (1024, 144, 512, 8, True), (256, 144, 512, 8, True),
    (64, 144, 512, 8, True), (32, 144, 512, 8, True),        # rungs, enrolment
    (8, 144, 128, 4, True), (8, 144, 256, 2, True),          # heads of 32, of 128
    (1024, 196, 512, 8, True), (1024, 256, 512, 8, True),    # 14 x 14 patches; the bound
    (1024, 257, 512, 8, False), (8, 400, 512, 8, False),     # scores past the VMEM
    (1024, 144, 768, 8, False),    # ViT-L: a head of 96 does not divide 128
    (1020, 144, 512, 8, False), (3, 144, 512, 8, False),     # a ragged N
    (1024, 144, 64, 4, False), (1024, 144, 192, 3, False),   # no whole lane group
    (1024, 144, 512, 2, False),    # a head wider than a lane group
    (1024, 144, 512, 3, False)])   # heads that do not divide the width
def test_the_predicate_s_table(n, tokens, width, heads, fits):
    assert vit_attention.fits(n, tokens, width, heads) == fits


def test_attend_lowers_to_xla_s_form_letter_for_letter_off_the_tpu():
    """Where the shapes fit the kernel ``attend`` binds ONE primitive, and
    lowered for the CPU that is XLA's form inline: the text of the function
    that never heard of the kernel, no custom call, the kernel's name
    nowhere. Where they do not fit, no primitive either."""
    x, kernel, _qkv = _inputs(8, 128, 2, seed=1, tokens=16)
    names = [e.primitive.name for e in jax.make_jaxpr(vit_attention.attend)(x, kernel).eqns]
    assert names == ["vit_attend"]
    text = jax.jit(vit_attention.attend).lower(x, kernel).as_text()
    assert "custom_call" not in text and vit_attention.NAME not in text
    assert text == jax.jit(vit_attention._attend_xla).lower(x, kernel).as_text().replace(
        "jit__attend_xla", "jit_attend")
    np.testing.assert_array_equal(
        np.asarray(vit_attention.attend(x, kernel), np.float32),   # outside any jit
        np.asarray(jax.jit(vit_attention._attend_xla)(x, kernel), np.float32))
    x, kernel, _qkv = _inputs(3, 128, 2, seed=1, tokens=16)
    names = [e.primitive.name for e in jax.make_jaxpr(vit_attention.attend)(x, kernel).eqns]
    assert "vit_attend" not in names and names.count("dot_general") == 5


@pytest.mark.parametrize("transform", ["grad", "vmap", "vmap_of_grad"])
def test_grad_and_vmap_go_through_attend_at_a_shape_the_kernel_takes(transform):
    """To ``vmap`` and to ``grad`` the primitive IS XLA's form (the kernel is
    the plain forward pass's: a Pallas call has no transpose): what they give
    through ``attend`` is what they give through XLA's form alone, bit for
    bit, and no primitive is left in what they trace."""
    x, kernel, _qkv = _inputs(8, 128, 2, seed=11, tokens=16)
    assert vit_attention.fits(*x.shape, 2)
    assert "vit_attend" in str(jax.make_jaxpr(vit_attention.attend)(x, kernel))
    assert "vit_attend" not in str(jax.make_jaxpr(
        jax.vmap(vit_attention.attend, in_axes=(0, None)))(x[None], kernel))

    def loss(f):
        return lambda a, k: jnp.sum(f(a, k).astype(jnp.float32) ** 2)

    def apply(f):
        if transform == "grad":
            return jax.jit(jax.grad(loss(f), argnums=(0, 1)))(x, kernel)
        xs = jnp.stack([x, 2 * x, -x])
        if transform == "vmap":
            return jax.jit(jax.vmap(f, in_axes=(0, None)))(xs, kernel)
        return jax.jit(jax.vmap(jax.grad(loss(f)), in_axes=(0, None)))(xs, kernel)

    ours, theirs = apply(vit_attention.attend), apply(vit_attention._attend_xla)
    for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and np.abs(np.asarray(b, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("n,tokens", [(3, 16), (8, 264)], ids=["ragged_n", "too_long"])
def test_the_kernel_refuses_a_shape_the_predicate_refuses(n, tokens):
    _x, _kernel, qkv = _inputs(n, 128, 2, seed=1, tokens=tokens)
    with pytest.raises(ValueError, match="does not take"):
        vit_attention.attention(qkv, 2, interpret=True)

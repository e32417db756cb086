"""Streaming pallas top-k matcher vs the lax.top_k oracle.

Runs in interpret mode on the CPU suite (SURVEY.md §4 prescription: every
kernel gets an oracle test); the last tests compile the kernels for the chip
without one (the attention kernel of ``ops.vit_attention`` among them: one
file may describe the topology), and ``chip_smoke.py`` runs the matcher
compiled on the chip.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.ops import pallas_match
from opencv_facerecognizer_tpu.ops.pallas_match import streaming_match_topk

RNG = np.random.default_rng(3)


def _oracle(q, g, valid, k):
    sims = q.astype(np.float32) @ g.astype(np.float32).T
    sims = np.where(np.asarray(valid)[None, :], sims, -1e30)
    idx = np.argsort(-sims, axis=1)[:, :k]
    return np.take_along_axis(sims, idx, axis=1), idx


def _normed(shape):
    x = RNG.normal(size=shape).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@pytest.mark.parametrize("qn,n,k", [(8, 256, 1), (16, 512, 4), (32, 1024, 8)])
def test_streaming_topk_matches_oracle(qn, n, k):
    q = _normed((qn, 64))
    g = _normed((n, 64))
    valid = np.ones(n, bool)
    vals, idx = streaming_match_topk(jnp.asarray(q), jnp.asarray(g),
                                     jnp.asarray(valid), k=k,
                                     block_q=8, block_n=128, interpret=True)
    ovals, _ = _oracle(q, g, valid, k)
    # bf16 matmul: compare values loosely, and exact given re-scored indices
    np.testing.assert_allclose(np.asarray(vals), ovals, atol=2e-2)
    rescored = np.take_along_axis(q @ g.T, np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.sort(rescored), np.sort(ovals), atol=2e-2)


def test_streaming_topk_masks_invalid_rows():
    q = _normed((8, 32))
    g = _normed((256, 32))
    valid = np.zeros(256, bool)
    valid[:7] = True  # fewer valid rows than would fill k on some tiles
    vals, idx = streaming_match_topk(jnp.asarray(q), jnp.asarray(g),
                                     jnp.asarray(valid), k=4,
                                     block_q=8, block_n=64, interpret=True)
    idx = np.asarray(idx)
    vals = np.asarray(vals)
    real = vals > -1e29
    assert np.all(idx[real] < 7), "an invalid gallery row surfaced"
    ovals, oidx = _oracle(q, g, valid, 4)
    np.testing.assert_allclose(vals[real], ovals[real.nonzero()[0],
                                                 real.nonzero()[1]], atol=2e-2)


def test_streaming_topk_unaligned_sizes():
    # Q and N not multiples of the blocks: padding path.
    q = _normed((13, 48))
    g = _normed((300, 48))
    valid = np.ones(300, bool)
    valid[250:] = False
    vals, idx = streaming_match_topk(jnp.asarray(q), jnp.asarray(g),
                                     jnp.asarray(valid), k=3,
                                     block_q=8, block_n=128, interpret=True)
    assert vals.shape == (13, 3) and idx.shape == (13, 3)
    ovals, _ = _oracle(q, g, valid, 3)
    np.testing.assert_allclose(np.asarray(vals), ovals, atol=2e-2)
    assert np.all(np.asarray(idx) < 250)


def test_streaming_topk_tie_break_prefers_lowest_index():
    """Deterministic tie-breaking parity (BENCH_r05: pallas-vs-XLA idx
    match 0.6914 with |sim diff| exactly 0 — pure tie-order divergence):
    on a tie-heavy gallery (every row duplicated many times, ties spanning
    multiple gallery tiles) the kernel must agree with a stable
    lowest-index-first oracle on EVERY index — idx match == 1.0."""
    base = _normed((4, 32))
    g = np.tile(base, (32, 1))  # 128 rows; each base row appears 32x,
    q = base                    # copies 4 apart -> ties cross block_n=32 tiles
    valid = np.ones(len(g), bool)
    vals, idx = streaming_match_topk(jnp.asarray(q), jnp.asarray(g),
                                     jnp.asarray(valid), k=4,
                                     block_q=8, block_n=32, interpret=True)
    sims = q @ g.T
    # Stable argsort == lax.top_k's documented tie order: lowest index
    # first among equal similarities.
    oidx = np.argsort(-sims, axis=1, kind="stable")[:, :4]
    idx = np.asarray(idx)
    assert (idx == oidx).mean() == 1.0, (idx, oidx)
    # And the tied values themselves survive exactly.
    ovals = np.take_along_axis(sims, oidx, axis=1)
    np.testing.assert_allclose(np.asarray(vals), ovals, atol=2e-2)


def test_streaming_topk_duplicate_scores_unique_indices():
    # Identical gallery rows: the k winners must be k distinct indices.
    g = np.tile(_normed((1, 16)), (64, 1)).astype(np.float32)
    q = g[:4]
    vals, idx = streaming_match_topk(jnp.asarray(q), jnp.asarray(g),
                                     jnp.ones(64, bool), k=4,
                                     block_q=8, block_n=32, interpret=True)
    idx = np.asarray(idx)
    for row in idx:
        assert len(set(row.tolist())) == 4, row


# ---- the layout of PR 27: whole-batch query block, gallery tiles of several
# products ("chunks") each, running bests per (row mod 8, query) slot ----

ROWS = pallas_match._SIDE  # gallery rows per MXU product at k = 1
TILE = 2 * ROWS  # block_n of these tests: two products a tile


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def _stable_oracle(q, g, valid, k):
    """Exact top-k of the bf16-rounded operands, ties to the lowest row."""
    with np.errstate(invalid="ignore"):  # an invalid row may hold inf or NaN
        sims = _bf16(q) @ _bf16(g).T
    sims = np.where(np.asarray(valid, bool)[None, :], sims, -1e30)
    idx = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(sims, idx, axis=1)
    return vals, np.where(vals > -1e29, idx, -1), sims


def _unit(rng, shape):
    x = rng.normal(size=shape).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _check(q, g, valid, k, *, exact_idx, **blocks):
    vals, idx = (np.asarray(v) for v in streaming_match_topk(
        jnp.asarray(q), jnp.asarray(g), jnp.asarray(valid), k=k,
        interpret=True, **blocks))
    ovals, oidx, sims = _stable_oracle(q, g, valid, k)
    assert vals.shape == ovals.shape and idx.dtype == np.int32
    empty = ovals < -1e29
    assert np.all(idx[empty] == -1) and np.all(vals[empty] == np.float32(-1e30))
    np.testing.assert_allclose(vals[~empty], ovals[~empty], atol=1e-5)
    assert np.all(np.asarray(valid, bool)[idx[~empty]]), "an invalid row surfaced"
    if exact_idx:
        assert (idx == oidx).mean() == 1.0, (idx, oidx)
    else:  # random rows: a near-tie may swap, the served row must score the same
        served = np.take_along_axis(sims, np.maximum(idx, 0), axis=1)
        np.testing.assert_allclose(served[~empty], ovals[~empty], atol=1e-5)
        for row in idx:
            real = row[row >= 0]
            assert len(set(real.tolist())) == len(real), row
    return vals, idx


def test_plan_derives_blocks_from_shapes():
    plan = pallas_match._plan
    # serving: the whole ladder batch resident, 4,096-row tiles, no padding
    assert plan(1024, 8388608, 256, 1, 2, None, None) == (1024, 4096, ROWS, 1024)
    assert plan(64, 65536, 256, 1, 2, None, None) == (128, 4096, ROWS, 128)
    # a larger batch comes back as blocks of 1,024; bounds under one lane
    # row are rounded up; a small gallery is one tile
    assert plan(4096, 65536, 256, 1, 2, None, None)[0] == 1024
    assert plan(8, 128, 32, 4, 4, 8, 32) == (128, 128, 128, 128)
    assert plan(13, 300, 48, 3, 4, 8, 128) == (128, 128, 128, 128)
    assert plan(13, 300, 48, 1, 4, None, None) == (128, 384, 384, 128)
    # the unrolled fold of one product stays the same size as k grows
    assert [plan(1024, 65536, 256, k, 2, None, None)[2:] for k in (1, 2, 4, 8, 64)] == [
        (ROWS, 1024), (ROWS // 2, 512), (ROWS // 4, 256), (ROWS // 8, 128), (ROWS // 8, 128)]
    assert plan(300, 65536, 256, 4, 2, None, None)[::3] == (512, 256)  # whole products
    # an IVF bucket: tiles cover N rounded up to 128 with little over
    bq, bn, rows, _ = plan(1024, 196709, 256, 5, 2, None, None)
    assert bn % rows == 0 and -(-196709 // bn) * bn - 196709 < bn
    # wide rows or many levels shrink the blocks to the VMEM budget
    for d, k, row_bytes in ((256, 1, 2), (256, 64, 2), (2048, 8, 4), (8192, 1, 4)):
        bq, bn, rows, lanes = plan(1024, 1 << 20, d, k, row_bytes, None, None)
        cand = -(-8 * k // 128) * 128
        held = (2 * d * bq * 2 + 2 * cand * bq * 4 + rows * lanes * 4
                + 2 * bn * (d * row_bytes + 4))
        assert bq % lanes == 0 and lanes % 128 == 0 and bn % rows == 0 and rows % 128 == 0
        assert held <= pallas_match._VMEM_BUDGET or (bq, bn) == (128, 128), (d, k, held)


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("qn,n", [(8, 3 * TILE), (37, 2 * TILE + 777),
                                  (130, TILE + 1)])
def test_new_layout_matches_stable_oracle(qn, n, k):
    """One resident query block over several gallery tiles of two products
    each; Q not a multiple of 8 (37) nor of 128 (130: two lane blocks), N
    not a multiple of 128; scattered invalid rows."""
    rng = np.random.default_rng(1000 * k + n + qn)
    q, g = _unit(rng, (qn, 32)), _unit(rng, (n, 32))
    valid = rng.random(n) > 0.1
    _check(q, g, valid, k, exact_idx=False, block_n=TILE)


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("apart", [4, 8, 128, ROWS, TILE])
def test_ties_across_slots_groups_products_and_tiles(apart, k):
    """Every row appears 2k times, copies ``apart`` rows from each other:
    4 = another slot of the same 8-row group, 8 = the same slot of the next
    group, 128, one product and one tile apart. Index equality 1.0 against
    a stable argsort: the k lowest copies of the best row, in order."""
    rng = np.random.default_rng(apart + k)
    copies, n = 2 * k, 2 * TILE + 2 * 8 * TILE // ROWS
    base = _unit(rng, (apart, 16))
    g = _unit(rng, (max(n, copies * apart), 16)) * 0.5  # filler scores lower
    g[:copies * apart] = np.tile(base, (copies, 1))
    q = base[rng.permutation(apart)[:min(apart, 24)]]
    valid = np.ones(len(g), bool)
    _, idx = _check(q, g, valid, k, exact_idx=True, block_n=TILE)
    assert np.all(np.diff(idx, axis=1) == apart)


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("hole", ["first_row", "last_row", "slot", "group",
                                  "product", "tile", "best_rows", "padding"])
def test_invalid_rows_in_every_position_class(hole, k):
    n = 3 * TILE - (100 if hole == "padding" else 0)
    rng = np.random.default_rng(len(hole) + k)
    q, g = _unit(rng, (16, 16)), _unit(rng, (n, 16))
    valid = np.ones(n, bool)
    if hole == "first_row":
        valid[[0, TILE, 2 * TILE]] = False
    elif hole == "last_row":
        valid[[TILE - 1, 2 * TILE - 1, n - 1]] = False
    elif hole == "slot":  # one row mod 8 everywhere
        valid[3::8] = False
    elif hole == "group":
        valid[TILE + 64:TILE + 72] = False
    elif hole == "product":
        valid[ROWS:2 * ROWS] = False
    elif hole == "tile":
        valid[TILE:2 * TILE] = False
    elif hole == "best_rows":  # what every query would have been served
        valid[np.argsort(-(q @ g.T), axis=1)[:, :k + 1].ravel()] = False
    # an invalid row may hold anything: it must not surface, nor poison a slot
    g[~valid] = np.where(rng.random((int((~valid).sum()), 1)) < 0.5, np.inf, np.nan)
    _check(q, g, valid, k, exact_idx=False, block_n=TILE)


@pytest.mark.parametrize("k,n_valid", [(1, 0), (4, 0), (4, 3), (8, 7), (8, 1)])
def test_fewer_valid_rows_than_k_leaves_sentinels(k, n_valid):
    rng = np.random.default_rng(k + n_valid)
    n = 2 * TILE
    q, g = _unit(rng, (9, 16)), _unit(rng, (n, 16))
    valid = np.zeros(n, bool)
    valid[rng.permutation(n)[:n_valid]] = True
    vals, idx = _check(q, g, valid, k, exact_idx=True, block_n=TILE)
    assert np.all((idx >= 0).sum(axis=1) == n_valid)
    assert np.all(idx[:, n_valid:] == -1) and np.all(vals[:, n_valid:] < -1e29)


def test_query_blocks_over_tiles_and_stored_bf16_rows():
    """Q over the block bound: an outer query axis re-reads the gallery per
    block and restarts the running bests; rows stored as bf16 stay bf16."""
    rng = np.random.default_rng(11)
    q, g = _unit(rng, (300, 32)), _unit(rng, (TILE + 300, 32))
    valid = rng.random(len(g)) > 0.05
    a = _check(q, g, valid, 4, exact_idx=False, block_q=128, block_n=ROWS)
    b = streaming_match_topk(jnp.asarray(q), jnp.asarray(g, jnp.bfloat16),
                             jnp.asarray(valid), k=4, interpret=True)
    for x, y in zip(a, b):  # one block and default tiles: the same answer
        np.testing.assert_array_equal(x, np.asarray(y))


@pytest.mark.parametrize("k", [1, 4, 8])
def test_agrees_with_match_global(k):
    """Same inputs through the XLA matcher: equal indices on a tie-heavy
    gallery with invalid rows, equal similarities to float rounding."""
    import jax
    from jax.sharding import Mesh

    from opencv_facerecognizer_tpu.parallel.gallery import match_global
    from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS

    rng = np.random.default_rng(7 + k)
    base = _unit(rng, (40, 32))
    g = np.tile(base, (2 * TILE // 40 + 1, 1))[:2 * TILE]
    q = base[:24]
    valid = rng.random(len(g)) > 0.3
    labels = np.arange(len(g), dtype=np.int32) % 40
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), (DP_AXIS, TP_AXIS))
    args = (jnp.asarray(q), jnp.asarray(g), jnp.asarray(valid))
    _, ref_v, ref_i = match_global(*args, jnp.asarray(labels), k=k, mesh=mesh)
    vals, idx = streaming_match_topk(*args, k=k, block_n=TILE, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_i))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_v), atol=1e-5)


# ---- the chip's compiler, without the chip (on-chip-measurement guide §2) ----

@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(four_chips[0])


@pytest.mark.parametrize("qn,n,k,d", [
    (1024, 8388608, 1, 256), (256, 8388608, 1, 256), (64, 8388608, 1, 256),
    (1024, 196709, 5, 256),
    # the 512-d rows of ``watchlist4m-r50``: same algorithm, 2,048-row tiles
    (1024, 4194304, 1, 512), (256, 4194304, 1, 512), (64, 4194304, 1, 512),
    (1024, 98355, 5, 512)])
def test_compiles_for_v5e_under_the_name_the_benchmark_reads(one_chip, qn, n, k, d):
    """Mosaic takes the kernel at the serving widths (every ladder rung over
    each benchmark gallery, and an IVF bucket with k > 1) inside the default
    VMEM limit, as ONE custom call whose name holds ``streaming_match_topk``
    and whose first output is f32[Q, k] — what the profiler's event, and
    with it the benchmark's roofline reader, is found by."""
    import re

    import jax

    shapes = (jax.ShapeDtypeStruct((qn, d), jnp.float32, sharding=one_chip),
              jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=one_chip),
              jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    text = jax.jit(lambda q, g, v: streaming_match_topk(q, g, v, k=k)).lower(
        *shapes).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    found = re.search(r"%(\S*streaming_match_topk\S*) = \(f32\[(\d+),(\d+)\]",
                      calls[0])
    assert found, calls[0]
    assert int(found.group(2)) == -(-qn // 128) * 128 and int(found.group(3)) == k


def test_iresnet_r50_compiles_for_v5e_at_the_top_rung(one_chip):
    """The published IResNet-50 over one top-rung step's 1,024 crops of
    112x112 fits the chip beside a 4.3 GB gallery (here with the kernels'
    compiles, because one test file may describe the topology: the
    on-chip-measurement guide, section 2)."""
    import jax

    from opencv_facerecognizer_tpu.models import iresnet

    net = iresnet.IResNet()
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *iresnet.R50_FACE_SIZE)))["params"]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    crops = jax.ShapeDtypeStruct((1024, *iresnet.R50_FACE_SIZE), jnp.float32,
                                 sharding=one_chip)
    memory = jax.jit(lambda p, x: net.apply({"params": p}, x)).lower(
        params, crops).compile().memory_analysis()
    assert memory.output_size_in_bytes == 1024 * 512 * 4
    assert memory.temp_size_in_bytes < 4 * 2**30, memory.temp_size_in_bytes


def test_vit_b_compiles_for_v5e_at_the_top_rung_with_the_attention_kernel(one_chip):
    """The published ViT-B over one top-rung step's 1,024 crops (147,456
    tokens through 24 unrolled blocks in one call), lowered for the v5e from
    this CPU host, reaches ``ops.vit_attention``'s kernel: the choice follows
    the lowering's platform, not ``jax.default_backend()``. One custom call a
    block whose name holds ``vit_attention`` under the scope ``vit_attn``
    (what the profiler's operation, and with it ``attn_device_ms``, is found
    by), no [N, heads, T, T] scores left in memory, and the net fits the chip
    beside a 4.3 GB gallery and its own 455 MB of float32 parameters: 0.73
    GiB of temporaries when this was written, 1.89 with XLA's attention (the
    f32 scores of a block were 0.63 GiB; the MLP's hidden layer is 0.56 GiB
    in bf16)."""
    import jax

    from opencv_facerecognizer_tpu.models import vit
    from opencv_facerecognizer_tpu.ops import vit_attention

    net = vit.ViT()
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *vit.VIT_B_FACE_SIZE)))["params"]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    crops = jax.ShapeDtypeStruct((1024, *vit.VIT_B_FACE_SIZE), jnp.float32,
                                 sharding=one_chip)
    lowered = jax.jit(lambda p, x: net.apply({"params": p}, x)).lower(params, crops)
    # what ``parallel.pipeline`` reads the step's form from: the kernel's name
    # once a block in the text lowered for the chip
    assert lowered.as_text().count(vit_attention.NAME) == net.depth
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == net.depth == 24
    for line in calls:
        assert line.lstrip().startswith("%vit_attention"), line[:80]
        assert "bf16[1024,144,512]" in line.split(" custom-call(")[0], line[:120]
        assert "/vit_attn/vit_attention" in line
    assert "f32[1024,8,144,144]" not in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 1024 * 512 * 4
    assert memory.argument_size_in_bytes > 113_832_960 * 4
    assert memory.temp_size_in_bytes < 3 * 2**30, memory.temp_size_in_bytes
    assert memory.temp_size_in_bytes < 1.89 * 2**30, memory.temp_size_in_bytes


@pytest.mark.parametrize("n", [64, 256, 1024])
def test_the_attention_kernel_compiles_for_v5e_inside_the_default_vmem_limit(one_chip, n):
    """Mosaic takes the kernel alone at the slots of every serving rung (8
    face slots a frame: 64, 256, 1,024 sequences of 144 tokens, 8 heads of
    64) with no VMEM limit asked for, as one custom call that reads the one
    qkv array three times and holds nothing in HBM beside its output."""
    import jax

    from opencv_facerecognizer_tpu.ops import vit_attention

    qkv = jax.ShapeDtypeStruct((n, 144, 3 * 512), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda a: vit_attention.attention(a, 8)).lower(qkv).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1 and "vit_attention" in calls[0], calls
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("side,tokens,kernel", [(144, 256, True), (180, 400, False)])
def test_a_longer_sequence_takes_the_kernel_up_to_its_bound_and_xla_s_form_beyond(
        one_chip, side, tokens, kernel):
    """``ViTEmbedding(input_size, patch)`` is configurable and a grid step
    holds 8 x T x T float32 scores: at patch 9 a crop of 144 x 144 is 256
    tokens, the longest the predicate offers the kernel (Mosaic takes it
    inside the default VMEM limit); 180 x 180 is 400, which Mosaic refuses
    (21.8 MiB of 16), so the predicate leaves it to XLA's form and the net
    compiles with no custom call, as it did before there was a kernel."""
    import jax

    from opencv_facerecognizer_tpu.models import vit
    from opencv_facerecognizer_tpu.ops import vit_attention

    net = vit.ViT(depth=1)
    assert net.tokens((side, side)) == tokens
    assert vit_attention.fits(8, tokens, net.embed_dim, net.heads) == kernel
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, side, side)))["params"]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    crops = jax.ShapeDtypeStruct((8, side, side), jnp.float32, sharding=one_chip)
    lowered = jax.jit(lambda p, x: net.apply({"params": p}, x)).lower(params, crops)
    assert (vit_attention.NAME in lowered.as_text()) == kernel
    calls = [line for line in lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == (1 if kernel else 0)


@pytest.mark.parametrize("how", ["jit_over_four_chips", "shard_map_over_four_chips"])
def test_on_a_mesh_the_kernel_runs_where_the_lowering_is_for_one_chip_s_share(four_chips, how):
    """XLA cannot partition a Mosaic kernel (jax refuses to lower one under a
    ``jit`` over several chips): there the TPU's rule gives XLA's form, which
    XLA partitions over dp as it did before there was a kernel, and the
    kernel's name is nowhere in the lowered text (so the dispatch's
    provenance says ``xla``); inside a ``shard_map`` over the whole mesh each
    chip's 16 sequences go through the kernel."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from opencv_facerecognizer_tpu.ops import vit_attention

    mesh = Mesh(np.array(four_chips).reshape(4, 1), ("dp", "tp"))
    rows, whole = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((64, 144, 512), jnp.bfloat16, sharding=rows)
    kernel = jax.ShapeDtypeStruct((512, 3, 8, 64), jnp.bfloat16, sharding=whole)
    attend = vit_attention.attend
    if how == "shard_map_over_four_chips":
        attend = jax.shard_map(attend, mesh=mesh, in_specs=(P("dp"), P()),
                               out_specs=P("dp"), check_vma=False)
    lowered = jax.jit(attend, out_shardings=rows).lower(x, kernel)
    kernel_on_path = how == "shard_map_over_four_chips"
    assert (vit_attention.NAME in lowered.as_text()) == kernel_on_path
    calls = [line for line in lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == (1 if kernel_on_path else 0)
    if kernel_on_path:
        assert "bf16[16,144,512]" in calls[0].split(" custom-call(")[0]


def test_the_gradient_through_the_attention_compiles_for_v5e(one_chip):
    """``jax.grad`` over ``attend`` at the published shape, lowered for the
    chip: XLA's form and its cotangents, no custom call (the kernel is the
    plain forward pass's; a Pallas call has no transpose)."""
    import jax

    from opencv_facerecognizer_tpu.ops import vit_attention

    x = jax.ShapeDtypeStruct((8, 144, 512), jnp.bfloat16, sharding=one_chip)
    kernel = jax.ShapeDtypeStruct((512, 3, 8, 64), jnp.bfloat16, sharding=one_chip)

    def loss(a, k):
        return jnp.sum(vit_attention.attend(a, k).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, kernel).compile()
    assert "tpu_custom_call" not in compiled.as_text()

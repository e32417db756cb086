"""Pallas TPU kernel: fused gallery similarity + streaming top-k.

The hot op of the serving path (SURVEY.md §3.4: the reference's
``NearestNeighbor.predict`` "distances to ALL gallery vectors -> argsort"
loop) is a [Q, D] x [D, N] similarity matmul followed by top-k. The XLA
formulation (``parallel.gallery.match_global``) materializes the [Q, N]
score matrix in HBM before ``lax.top_k`` reads it back — at Q=256 over a
1M-row gallery that is a 1 GB f32 round-trip per batch, pure HBM-bandwidth
waste for k<=8 survivors per query.

This kernel reads the gallery ONCE per call and never builds a score
matrix anywhere:

* **Tiling.** The whole query batch (up to 1,024 queries; more only come
  back as an outer grid axis of 1,024-query blocks) is one resident block:
  cast to bf16 and transposed to [D, Q] once, outside the kernel. The grid
  runs over gallery tiles of a few thousand rows (sized from Q, D, k and
  the row dtype against a VMEM budget, a few MB double-buffered), so a
  call is a few thousand grid steps and the per-step cost vanishes.
* **Orientation.** The gallery rows are the operand that streams through
  the MXU and the queries are its stationary weights: a step computes
  ``scores^T = rows [R, D] @ queries^T [D, W]`` product by product (bf16
  operands, f32 accumulation, the precision split of the XLA path) — at
  k = 1 R = 1,024 rows, long enough to amortise latching the queries,
  against all W <= 1,024 resident queries. Queries lie on lanes, gallery
  rows on sublanes.
* **Fold.** A product's [8, W] slices (8 consecutive gallery rows x W
  queries) are folded one by one, elementwise, into running bests that
  stay in registers for the whole product and on chip for the whole call:
  per (row mod 8, query) slot the best value so far and the id of the
  8-row group it came from — one ``min`` against the row's ceiling
  (``valid``), one strict ``>``, two selects. No concatenate, no
  per-element iota (the row is ``group * 8 + sublane``, rebuilt at the
  end), no reduction. Groups are visited in ascending order and the
  compare is strict, so within a slot the earliest row survives a tie.
  For k > 1 a slot keeps k sorted levels (an insertion: the levels under
  the first one the new value beats shift down by one) and a product
  shrinks with k, R = W = 1024 / k rounded down to 512, 256 or 128, so
  the unrolled fold keeps its size; the global top-k is contained in the
  union of every slot's top-k.
* **Extract, once.** On the last gallery tile the [8k, 128] candidates of
  each 128 queries are transposed and the k max-extract passes (max,
  lowest row among equals, ``-1`` from the value) run once per call, not
  once per tile.

* **Width.** Nothing is special to one D: ``_plan`` sizes the resident
  queries (2 * D * Q bytes, double-buffered) and the gallery tile from D
  against the VMEM budget. Doubling D doubles a row's bytes and its
  multiply-adds, so N rows at D = 512 cost what 2N rows cost at 256, in
  HBM and in time; the tile halves (2,048 rows at Q = 1,024, D = 512, bf16;
  4,096 at D = 256) and the product stays 1,024 rows; wider rows yet
  shrink the product (512 rows at D = 1,024) and then the query block
  (512 queries at D = 2,048). On the chip: 96.1 % of the MXU's floor
  at Q = 1,024 over 4,194,304 x 512 rows, 95.6 % over 8,388,608 x 256
  (PERF.md).

Used by ``ShardedGallery`` as the single-shard fast path and by
``ops.ivf_match`` to rerank its bucket; the XLA formulation stays both the
multi-chip GSPMD path (XLA cannot partition a custom call across tp
shards) and the correctness oracle in tests, which run this kernel in
interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # plain float: a jnp scalar would be a captured constant in the kernel
_NO_CEILING = 3.0e38  # ceiling of a valid row: min(score, this) is the score
_LANES = 128  # the lane width: queries and gallery rows are padded to multiples of it
_SUBLANES = 8  # gallery rows per f32 register: the slots a query's running bests live in
_MAX_BLOCK_Q = 1024  # queries resident at once; a larger batch re-reads the gallery per block
_SIDE = 1024  # gallery rows, and at most queries, per MXU product at k = 1: rows measured best of 128..2048 at every ladder rung
_TILE_ROWS = 4096  # gallery rows per grid step where VMEM allows (2 MB of bf16 at D=256)
_VMEM_BUDGET = 12 * 2**20  # of the 16 MiB a kernel may use by default; the rest is Mosaic's


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _plan(qn: int, n: int, d: int, k: int, row_bytes: int,
          block_q: int | None, block_n: int | None):
    """(block_q, block_n, rows and queries per product) from the shapes.

    ``block_q``/``block_n`` are upper bounds; what the layout cannot take
    is rounded up to a multiple of 128. The query block shrinks only when
    its own buffers (queries + running bests) would take over half the
    budget; the gallery tile gets the rest, double-buffered.
    """
    bq = min(_round_up(qn, _LANES), _round_up(block_q or _MAX_BLOCK_Q, _LANES),
             _MAX_BLOCK_Q)
    cand = _round_up(_SUBLANES * k, _LANES)

    def resident(b):  # queries (bf16, double-buffered) + both accumulators
        return 2 * d * b * 2 + 2 * cand * b * 4

    while bq > _LANES and resident(bq) > _VMEM_BUDGET // 2:
        bq = _round_up(bq // 2, _LANES)
    # The fold of a product is unrolled: one insertion of k levels per 8
    # rows, over all of the product's queries at once. Fewer rows and
    # fewer queries per product as k grows keep its code the same size
    # and its running bests (2k registers per 128 queries) in registers.
    side = max(_LANES, _SIDE // k // _LANES * _LANES)  # 1024, 512, 256, 256, 128, ...
    lanes = min(bq, side)
    bq = _round_up(bq, lanes)
    # one product's f32 scores and ceilings live in VMEM beside the tile
    spare = _VMEM_BUDGET - resident(bq) - side * (lanes + _LANES) * 4
    fit = max(_LANES, spare // (2 * (d * row_bytes + 4)))
    cap = min(_round_up(block_n or _TILE_ROWS, _LANES), fit // _LANES * _LANES)
    n128 = _round_up(n, _LANES)
    rows = min(side, cap, n128)
    cap = cap // rows * rows
    tiles = -(-n128 // cap)
    return bq, _round_up(-(-n128 // tiles), rows), rows, lanes


def _match_kernel(qt_ref, g_ref, valid_ref, vals_ref, idx_ref, best_v, best_g,
                  *, k: int, rows: int):
    """One (query-block, gallery-tile) grid step.

    qt_ref [D, BQ] bf16; g_ref [BN, D]; valid_ref [BN/128, 128] f32 (0/1,
    row r of the tile at [r // 128, r % 128]); vals_ref/idx_ref [BQ, k],
    written on the last tile. best_v/best_g [BQ/W, C, W] scratch: for
    each product's W queries (lanes) the running bests, level l of slot s
    (row mod 8) at sublane 8*l + s — value, and id of the 8-row group;
    rows past 8*k only pad C to the 128 a transpose takes.
    """
    j = pl.program_id(1)
    bn = g_ref.shape[0]
    slabs, _, lanes = best_v.shape

    @pl.when(j == 0)
    def _():
        best_v[...] = jnp.full(best_v.shape, NEG_INF, jnp.float32)
        best_g[...] = jnp.full(best_g.shape, -1, jnp.int32)

    def fold(c, carry):
        r0 = pl.multiple_of(c * rows, rows)
        g = g_ref[pl.ds(r0, rows), :].astype(jnp.bfloat16)
        # Per-row ceiling, broadcast along lanes: NEG_INF for an invalid
        # row (min() then hides whatever it holds, NaN and inf included),
        # no ceiling for a valid one. ``valid`` arrives lane-major; one
        # transpose per 128 rows turns it, shared by every query.
        ceil = []
        for p in range(rows // _LANES):
            v = valid_ref[pl.ds(c * (rows // _LANES) + p, 1), :]
            v = jnp.where(v > 0.5, _NO_CEILING, NEG_INF)
            ceil.append(jnp.broadcast_to(v, (_LANES, _LANES)).T)
        ceil = jnp.concatenate(ceil, axis=0) if len(ceil) > 1 else ceil[0]
        if lanes > _LANES:
            ceil = jnp.concatenate([ceil] * (lanes // _LANES), axis=1)
        group0 = (j * bn + r0) // _SUBLANES
        for slab in range(slabs):
            # MXU: bf16 operands, f32 accumulation (same precision split
            # as the XLA path in parallel.gallery.match_global).
            s = jnp.dot(g, qt_ref[:, slab * lanes:(slab + 1) * lanes],
                        preferred_element_type=jnp.float32)  # [rows, lanes]
            lv = [best_v[slab, l * _SUBLANES:(l + 1) * _SUBLANES, :]
                  for l in range(k)]
            lg = [best_g[slab, l * _SUBLANES:(l + 1) * _SUBLANES, :]
                  for l in range(k)]
            for r in range(rows // _SUBLANES):
                at = slice(r * _SUBLANES, (r + 1) * _SUBLANES)
                x = jnp.minimum(s[at, :], ceil[at, :])
                xg = group0 + r
                # Sorted insertion. Levels descend, so ``beats`` is
                # monotone: the first level x beats takes x, every level
                # under it takes its upper neighbour. Strict ``>`` and
                # ascending visits keep equal values in row order.
                beats = [x > v for v in lv]
                nv = [jnp.where(beats[0], x, lv[0])]
                ng = [jnp.where(beats[0], xg, lg[0])]
                for l in range(1, k):
                    nv.append(jnp.where(beats[l - 1], lv[l - 1],
                                        jnp.where(beats[l], x, lv[l])))
                    ng.append(jnp.where(beats[l - 1], lg[l - 1],
                                        jnp.where(beats[l], xg, lg[l])))
                lv, lg = nv, ng
            for l in range(k):
                best_v[slab, l * _SUBLANES:(l + 1) * _SUBLANES, :] = lv[l]
                best_g[slab, l * _SUBLANES:(l + 1) * _SUBLANES, :] = lg[l]
        return carry

    jax.lax.fori_loop(0, bn // rows, fold, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        cand = best_v.shape[1]
        slot = jax.lax.broadcasted_iota(jnp.int32, (cand, _LANES), 0) % _SUBLANES
        for lo in range(0, slabs * lanes, _LANES):
            at = (lo // lanes, slice(None), slice(lo % lanes, lo % lanes + _LANES))
            vals, row = best_v[at], best_g[at] * _SUBLANES + slot
            # Queries back onto sublanes: [128, C] candidates per query.
            cand_vals = jnp.concatenate(
                [vals[b:b + _LANES, :].T for b in range(0, cand, _LANES)],
                axis=1)
            cand_idx = jnp.concatenate(
                [row[b:b + _LANES, :].T for b in range(0, cand, _LANES)],
                axis=1)
            new_vals, new_idx = [], []
            for _ in range(k):  # k is small and static: unrolled max-extracts
                best = jnp.max(cand_vals, axis=1, keepdims=True)  # [128, 1]
                # Deterministic tie-breaking: among candidates at the max
                # value, take the LOWEST gallery index — the order of
                # lax.top_k / a stable argsort (a compiled TPU argmax picks
                # an unspecified tied position: idx-parity 0.69 vs XLA on
                # tie-heavy galleries with |sim diff| exactly 0).
                masked_idx = jnp.where(cand_vals == best, cand_idx,
                                       jnp.int32(2**31 - 1))
                best_idx = jnp.min(masked_idx, axis=1, keepdims=True)
                hit = (cand_vals == best) & (cand_idx == best_idx)
                # Sentinel from the VALUE, never from tie-breaking: when
                # all remaining candidates are empty (-1e30), the winner
                # above is whatever index rode that value — so a slot
                # whose best is the mask value must emit index -1
                # explicitly. Real sims are cosine-scale; half the mask
                # magnitude separates them unambiguously.
                best_idx = jnp.where(best > NEG_INF * 0.5, best_idx, -1)
                new_vals.append(best)
                new_idx.append(best_idx)
                cand_vals = jnp.where(hit, NEG_INF, cand_vals)
            vals_ref[lo:lo + _LANES, :] = jnp.concatenate(new_vals, axis=1)
            idx_ref[lo:lo + _LANES, :] = jnp.concatenate(new_idx, axis=1)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_n", "interpret")
)
def streaming_match_topk(q, g, valid, *, k: int = 1,
                         block_q: int | None = None,
                         block_n: int | None = None,
                         interpret: bool = False):
    """Top-k cosine/dot similarity of queries against a gallery, streamed.

    q [Q, D] float; g [N, D] float; valid [N] bool/0-1 mask.
    Returns (sims [Q, k] f32, indices [Q, k] int32); invalid rows never
    surface. Equal similarities break toward the LOWEST gallery index —
    the same order as ``lax.top_k`` and a stable argsort — so parity with
    the XLA matcher is exact even on tie-heavy (duplicate-row) galleries. When fewer than k valid rows exist, the empty slots carry
    sim -1e30 and the explicit sentinel index **-1** (derived from the
    value in-kernel, so it holds in compiled mode too) — callers gathering
    labels must mask ``idx < 0`` (see ``parallel.gallery``).

    The tiling is derived from the shapes (``_plan``): queries are cast to
    bf16 once and stay resident as one block of up to 1,024 (a larger
    batch, or a D or k whose buffers would not fit VMEM, brings back an
    outer query axis and re-reads the gallery per block); the gallery is
    read once per query block in tiles of up to 4,096 rows. Q is padded
    to a multiple of 128 and N to a whole number of tiles here, so any
    sizes work — a small gallery or an IVF bucket is one or a few tiles.
    ``block_q``/``block_n`` are upper bounds on the two blocks (the tests
    force several tiles with them); a bound under 128 is rounded up to it.
    """
    # Keep a bf16-stored gallery in bf16: the kernel feeds the MXU bf16
    # anyway, so upcasting here would only double the HBM traffic this
    # streaming kernel exists to save. Other dtypes go to f32 as before
    # and are cast tile by tile in the kernel.
    if g.dtype != jnp.bfloat16:
        g = jnp.asarray(g, jnp.float32)
    qn, d = q.shape
    n = g.shape[0]
    bq, bn, rows, lanes = _plan(qn, n, d, k, g.dtype.itemsize, block_q,
                                block_n)
    q_pad = (-qn) % bq
    n_pad = (-n) % bn
    qt = jnp.pad(jnp.asarray(q, jnp.float32).astype(jnp.bfloat16),
                 ((0, q_pad), (0, 0))).T
    if n_pad:
        g = jnp.pad(g, ((0, n_pad), (0, 0)))
    validf = jnp.pad(jnp.asarray(valid, jnp.float32), (0, n_pad)).reshape(
        -1, bn // _LANES, _LANES)
    cand = _round_up(_SUBLANES * k, _LANES)
    vals, idx = pl.pallas_call(
        functools.partial(_match_kernel, k=k, rows=rows),
        grid=(qt.shape[1] // bq, g.shape[0] // bn),
        in_specs=[
            pl.BlockSpec((d, bq), lambda i, j: (0, i)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((None, bn // _LANES, _LANES), lambda i, j: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qt.shape[1], k), jnp.float32),
            jax.ShapeDtypeStruct((qt.shape[1], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq // lanes, cand, lanes), jnp.float32),
            pltpu.VMEM((bq // lanes, cand, lanes), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        # The profiler's event, and the benchmark's roofline reader, find
        # the kernel by this name.
        name="streaming_match_topk",
        interpret=interpret,
    )(qt, g, validf)
    return vals[:qn], idx[:qn]

"""On-device non-maximum suppression with static shapes (SURVEY.md §7.6).

XLA needs static shapes, so NMS takes exactly K candidate boxes (padded
upstream) and has no dynamic output size anywhere: the whole detector decode
stays inside one jitted graph and batches under vmap. Two forms of the same
greedy rule. ``nms_mask`` sweeps all K candidates and returns the boolean
keep-mask (K loop steps; the plain form, and the tests' reference).
``nms_fixed``, which the detectors' decodes call, returns the
``max_outputs`` best kept boxes and costs ``max_outputs`` steps: the n-th box
greedy NMS keeps is the best-scored candidate the n-1 before it left
standing, so it selects that one n times and never decides the rest.

Boxes are [y0, x0, y1, x1] in any consistent unit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def box_area(boxes: jnp.ndarray) -> jnp.ndarray:
    h = jnp.maximum(boxes[..., 2] - boxes[..., 0], 0.0)
    w = jnp.maximum(boxes[..., 3] - boxes[..., 1], 0.0)
    return h * w


def pairwise_iou(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[K, 4], [M, 4] -> [K, M] IoU."""
    y0 = jnp.maximum(a[:, None, 0], b[None, :, 0])
    x0 = jnp.maximum(a[:, None, 1], b[None, :, 1])
    y1 = jnp.minimum(a[:, None, 2], b[None, :, 2])
    x1 = jnp.minimum(a[:, None, 3], b[None, :, 3])
    inter = jnp.maximum(y1 - y0, 0.0) * jnp.maximum(x1 - x0, 0.0)
    union = box_area(a)[:, None] + box_area(b)[None, :] - inter
    return inter / jnp.maximum(union, 1e-12)


def nms_mask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_threshold: float = 0.45,
    score_threshold: float = 0.0,
) -> jnp.ndarray:
    """Greedy NMS as a fixed-K boolean mask (True = kept).

    Candidates are visited in descending score order; a box is kept iff no
    already-kept, higher-scored box overlaps it above ``iou_threshold``.
    O(K^2) IoU + a K-step ``fori_loop``, fully jittable/vmappable.
    """
    k = boxes.shape[0]
    order = jnp.argsort(-scores)
    boxes_sorted = jnp.take(boxes, order, axis=0)
    scores_sorted = jnp.take(scores, order)
    iou = pairwise_iou(boxes_sorted, boxes_sorted)
    candidate = scores_sorted > score_threshold
    idx = jnp.arange(k)

    def body(i, keep):
        overlapped = keep & (idx < i) & (iou[i] > iou_threshold)
        return keep.at[i].set(candidate[i] & ~jnp.any(overlapped))

    keep_sorted = jax.lax.fori_loop(0, k, body, candidate)
    # Scatter back to original candidate order.
    keep = jnp.zeros((k,), dtype=bool).at[order].set(keep_sorted)
    return keep


def nms_fixed(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    max_outputs: int,
    iou_threshold: float = 0.45,
    score_threshold: float = 0.0,
):
    """NMS returning exactly ``max_outputs`` (boxes, scores, valid-mask),
    best first; unused slots are zero boxes with -inf score.

    Selects instead of sweeping. Greedy NMS visits candidates best first and
    keeps one iff no kept box overlaps it, so the n-th box it keeps is the
    best-scored candidate that none of the n-1 kept before it suppressed
    (lowest index on a tie, as the stable sort visits them). Each step takes
    that candidate, writes it to the next slot and strikes every candidate
    whose IoU with it exceeds ``iou_threshold`` (``pairwise_iou`` of all
    candidates against the one box: the comparisons the [K, K] matrix would
    have made). After ``max_outputs`` steps the outputs are what ``nms_mask``
    followed by the ``max_outputs`` best of the kept gives, bit for bit; what
    the sweep would decide about the other candidates is never returned, and
    here never computed. Cost: one loop of min(``max_outputs``, K) steps of
    O(K), its trip count static; no sort, no [K, K] matrix, no gather.
    """
    k = boxes.shape[0]
    steps = min(int(max_outputs), k)
    idx = jnp.arange(k)

    def select(open_, _):
        live = jnp.where(open_, scores, -jnp.inf)
        taken = idx == jnp.argmax(live)  # the first index of the maximum: the tie order
        # The one taken row, exactly: max(-inf, x) is x. With nothing open the
        # row is arbitrary, its slot reads -inf and it strikes nothing open.
        box = jnp.max(jnp.where(taken[:, None], boxes, -jnp.inf), axis=0)
        overlaps = pairwise_iou(boxes, box[None, :])[:, 0] > iou_threshold
        return open_ & ~overlaps & ~taken, (box, jnp.max(live))

    _, (top_boxes, top_scores) = jax.lax.scan(select, scores > score_threshold, None, length=steps)
    unused = max_outputs - steps
    top_boxes = jnp.pad(top_boxes, ((0, unused), (0, 0)))
    top_scores = jnp.pad(top_scores, (0, unused), constant_values=-jnp.inf)
    valid = jnp.isfinite(top_scores)
    return (
        jnp.where(valid[:, None], top_boxes, 0.0),
        jnp.where(valid, top_scores, -jnp.inf),
        valid,
    )

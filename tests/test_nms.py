"""On-device NMS vs brute-force oracle (SURVEY.md §4 prescription)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.ops import nms as N

RNG = np.random.default_rng(13)


def brute_force_nms(boxes, scores, iou_t, score_t):
    def iou(a, b):
        y0, x0 = max(a[0], b[0]), max(a[1], b[1])
        y1, x1 = min(a[2], b[2]), min(a[3], b[3])
        inter = max(y1 - y0, 0) * max(x1 - x0, 0)
        area = lambda z: max(z[2] - z[0], 0) * max(z[3] - z[1], 0)
        return inter / max(area(a) + area(b) - inter, 1e-12)

    order = np.argsort(-scores)
    kept = []
    for i in order:
        if scores[i] <= score_t:
            continue
        if all(iou(boxes[i], boxes[j]) <= iou_t for j in kept):
            kept.append(i)
    return sorted(kept)


def _random_boxes(k=40, rng=RNG):
    y0 = rng.uniform(0, 60, k)
    x0 = rng.uniform(0, 60, k)
    h = rng.uniform(5, 30, k)
    w = rng.uniform(5, 30, k)
    boxes = np.stack([y0, x0, y0 + h, x0 + w], axis=1).astype(np.float32)
    scores = rng.uniform(0, 1, k).astype(np.float32)
    return boxes, scores


def test_pairwise_iou_oracle():
    a, _ = _random_boxes(10)
    b, _ = _random_boxes(7)
    got = np.asarray(N.pairwise_iou(a, b))
    for i in range(10):
        for j in range(7):
            yi0, xi0 = max(a[i, 0], b[j, 0]), max(a[i, 1], b[j, 1])
            yi1, xi1 = min(a[i, 2], b[j, 2]), min(a[i, 3], b[j, 3])
            inter = max(yi1 - yi0, 0) * max(xi1 - xi0, 0)
            area_a = (a[i, 2] - a[i, 0]) * (a[i, 3] - a[i, 1])
            area_b = (b[j, 2] - b[j, 0]) * (b[j, 3] - b[j, 1])
            want = inter / (area_a + area_b - inter)
            np.testing.assert_allclose(got[i, j], want, rtol=1e-4, atol=1e-5)


def test_nms_mask_matches_bruteforce():
    for trial in range(5):
        boxes, scores = _random_boxes(40)
        keep = np.asarray(N.nms_mask(boxes, scores, 0.4, 0.1))
        want = brute_force_nms(boxes, scores, 0.4, 0.1)
        assert sorted(np.flatnonzero(keep).tolist()) == want, f"trial {trial}"


def test_nms_fixed_output_shapes_and_order():
    boxes, scores = _random_boxes(30)
    out_boxes, out_scores, valid = (np.asarray(v) for v in N.nms_fixed(boxes, scores, 8, 0.4, 0.1))
    assert out_boxes.shape == (8, 4) and out_scores.shape == (8,) and valid.shape == (8,)
    vs = out_scores[valid]
    assert np.all(np.diff(vs) <= 1e-6)  # descending
    assert np.all(out_boxes[~valid] == 0.0)


def test_nms_all_below_threshold():
    boxes, scores = _random_boxes(10)
    _, out_scores, valid = N.nms_fixed(boxes, scores * 0.01, 4, 0.4, 0.5)
    assert not np.any(np.asarray(valid))


def test_nms_identical_boxes_keep_one():
    box = np.array([[10, 10, 30, 30]] * 5, dtype=np.float32)
    scores = np.array([0.9, 0.8, 0.7, 0.6, 0.5], dtype=np.float32)
    keep = np.asarray(N.nms_mask(box, scores, 0.5, 0.0))
    assert keep.sum() == 1 and keep[0]


# ---- nms_fixed selects; the sweep (nms_mask, mask, the best) is the reference ----


def _sweep_fixed(boxes, scores, max_outputs, iou_t, score_t):
    """The formulation nms_fixed had before it selected: the all-K keep mask,
    the scores masked by it, the ``max_outputs`` best (slots past K unused)."""
    boxes, scores = jnp.asarray(boxes), jnp.asarray(scores)
    pad = max(max_outputs - boxes.shape[0], 0)
    keep = N.nms_mask(boxes, scores, iou_t, score_t)
    masked = jnp.concatenate([jnp.where(keep, scores, -jnp.inf), jnp.full((pad,), -jnp.inf)])
    top_scores, top_idx = jax.lax.top_k(masked, max_outputs)
    top_boxes = jnp.take(jnp.concatenate([boxes, jnp.zeros((pad, 4))]), top_idx, axis=0)
    valid = jnp.isfinite(top_scores)
    return (jnp.where(valid[:, None], top_boxes, 0.0), jnp.where(valid, top_scores, -jnp.inf), valid)


def _seeded_boxes(seed, k):
    """A case's own draw, whichever tests ran before it."""
    return _random_boxes(k, np.random.default_rng(seed))


def _case_random(k, max_outputs):
    return (*_seeded_boxes(1000 + k + max_outputs, k), max_outputs, 0.4, 0.1)


def _case_ties(k, saturated):
    boxes, scores = _seeded_boxes(2000 + k, k)
    scores = np.ones_like(scores) if saturated else np.round(scores, 2)
    return boxes, scores, 8, 0.4, 0.1


def _case_few_survivors():
    # 32 candidates in three tight clusters: three survive, five slots stay unused.
    rng = np.random.default_rng(3)
    corner = np.repeat(np.array([[0, 0], [40, 40], [80, 0]], np.float32), [11, 11, 10], axis=0)
    corner = corner + rng.uniform(0, 1, corner.shape).astype(np.float32)
    return (np.concatenate([corner, corner + 20], axis=1), rng.uniform(0.2, 1, 32).astype(np.float32),
            8, 0.4, 0.1)


def _case_nothing_over_threshold():
    boxes, scores = _seeded_boxes(4, 32)
    return boxes, scores * 0.3, 8, 0.4, 0.5


def _case_zero_area():
    # A zero-area box overlaps nothing, itself included: it is taken once all the same.
    boxes, scores = _seeded_boxes(5, 32)
    boxes[::3, 2:] = boxes[::3, :2]
    boxes[1::6, 2] = boxes[1::6, 0]
    return boxes, scores, 8, 0.4, 0.1


def _case_chain():
    # a > b > c: a suppresses b, only b overlaps c over the threshold, so c is kept.
    boxes = np.array([[0, 0, 10, 10], [0, 4, 10, 14], [0, 8, 10, 18], [50, 50, 60, 60]], np.float32)
    return boxes, np.array([0.9, 0.8, 0.7, 0.05], np.float32), 3, 0.4, 0.1


FIXED_CASES = {
    **{f"random-K{k}-out{m}": (_case_random, (k, m))
       for k in (32, 256) for m in (1, 8, k, k + 4)},
    **{f"ties-two-decimals-K{k}": (_case_ties, (k, False)) for k in (32, 256)},
    **{f"ties-saturated-K{k}": (_case_ties, (k, True)) for k in (32, 256)},
    "fewer-survivors-than-outputs": (_case_few_survivors, ()),
    "nothing-over-threshold": (_case_nothing_over_threshold, ()),
    "zero-area-boxes": (_case_zero_area, ()),
    "chain-a-b-c": (_case_chain, ()),
}


def _assert_same(got, want):
    for name, g, w in zip(("boxes", "scores", "valid"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.array_equal(g, w), name


@pytest.mark.parametrize("mode", ["plain", "jit"])
@pytest.mark.parametrize("case", sorted(FIXED_CASES))
def test_nms_fixed_equals_the_sweep(case, mode):
    make, args = FIXED_CASES[case]
    boxes, scores, max_outputs, iou_t, score_t = make(*args)
    fn = functools.partial(N.nms_fixed, max_outputs=max_outputs, iou_threshold=iou_t,
                           score_threshold=score_t)
    got = (jax.jit(fn) if mode == "jit" else fn)(boxes, scores)
    want = _sweep_fixed(boxes, scores, max_outputs, iou_t, score_t)
    _assert_same(got, want)
    out_boxes, out_scores, valid = (np.asarray(v) for v in got)
    assert out_boxes.shape == (max_outputs, 4) and valid.shape == (max_outputs,)
    assert np.all(out_boxes[~valid] == 0.0) and np.all(np.isneginf(out_scores[~valid]))
    if case == "chain-a-b-c":
        assert out_scores.tolist() == pytest.approx([0.9, 0.7, -np.inf])
    if case == "fewer-survivors-than-outputs":
        assert valid.sum() == 3
    if case == "nothing-over-threshold":
        assert not valid.any()


@pytest.mark.parametrize("k", [32, 256])
def test_nms_fixed_equals_the_sweep_under_vmap(k):
    rng = np.random.default_rng(k)
    boxes, scores = (np.stack(v) for v in zip(*(_random_boxes(k, rng) for _ in range(128))))
    scores = np.round(scores, 2)
    scores[64:] = np.where(scores[64:] > 0.5, 1.0, scores[64:])  # half the images saturate
    got = jax.jit(jax.vmap(lambda b, s: N.nms_fixed(b, s, 8, 0.4, 0.1)))(boxes, scores)
    want = jax.jit(jax.vmap(lambda b, s: _sweep_fixed(b, s, 8, 0.4, 0.1)))(boxes, scores)
    _assert_same(got, want)
    assert np.asarray(got[2]).all()


def _loops(jaxpr):
    """(primitive, trip count or None) of every loop in a jaxpr, inner ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(("scan", eqn.params["length"]))
        elif eqn.primitive.name == "while":
            found.append(("while", None))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_loops(inner))
    return found


def _scrfd_decode_jaxpr(max_faces, pre_nms):
    from opencv_facerecognizer_tpu.models import scrfd

    frame = (480, 640)
    cells = [(frame[0] // s, frame[1] // s) for s in scrfd.STRIDES]
    outputs = {
        "cls": tuple(jax.ShapeDtypeStruct((2, h, w, 2), jnp.float32) for h, w in cells),
        "reg": tuple(jax.ShapeDtypeStruct((2, h, w, 2, 4), jnp.float32) for h, w in cells),
    }
    return jax.make_jaxpr(lambda o: scrfd.decode(o, frame, max_faces, pre_nms=pre_nms))(outputs)


def _heatmap_decode_jaxpr(max_faces, pre_nms):
    from opencv_facerecognizer_tpu.models import detector

    assert pre_nms == 4 * max_faces
    outputs = {
        "heatmap": jax.ShapeDtypeStruct((2, 32, 32), jnp.float32),
        "size": jax.ShapeDtypeStruct((2, 32, 32, 2), jnp.float32),
        "offset": jax.ShapeDtypeStruct((2, 32, 32, 2), jnp.float32),
    }
    return jax.make_jaxpr(lambda o: detector.decode_detections(o, max_faces=max_faces))(outputs)


@pytest.mark.parametrize("decode_jaxpr,max_faces,pre_nms", [
    (_scrfd_decode_jaxpr, 8, 256),
    (_heatmap_decode_jaxpr, 8, 32),
], ids=["scrfd.decode", "decode_detections"])
def test_decode_loops_over_max_faces_not_over_candidates(decode_jaxpr, max_faces, pre_nms):
    """The served decodes hold one loop, of ``max_faces`` selections: nothing
    in them runs once a candidate (the sweep's ``fori_loop`` is a scan of K)."""
    loops = _loops(decode_jaxpr(max_faces, pre_nms).jaxpr)
    assert loops == [("scan", max_faces)], loops
    # The probe does see the sweep's loop where it is.
    sweep = jax.make_jaxpr(lambda b, s: N.nms_mask(b, s))(
        jax.ShapeDtypeStruct((pre_nms, 4), jnp.float32), jax.ShapeDtypeStruct((pre_nms,), jnp.float32))
    assert ("scan", pre_nms) in _loops(sweep.jaxpr)

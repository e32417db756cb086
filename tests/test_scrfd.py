"""The SCRFD detector: the flax net against the benchmark's plain reference
(``benchmark/configs/watchlist4m-scrfd-r50_reference.py``, float32 at
highest precision, no flax) at every level, its static-shape decode against
the reference's ``numpy`` anchor decode and NMS on planted outputs, its
counts at the published sizes, its checkpoint told by the header beside
``CNNFaceDetector``'s, one fused step behind the ``Detector`` boundary
against the stages run one by one (either detector class), the
``detect_frames`` counter, and a few training steps. Everything on seeded
random weights with no norm's statistic at its default, at CPU size."""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.apps import recognize as recognize_app
from opencv_facerecognizer_tpu.models import scrfd
from opencv_facerecognizer_tpu.models.cascade import FaceGate
from opencv_facerecognizer_tpu.models.classifier import NearestNeighbor
from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
from opencv_facerecognizer_tpu.models.embedder import CNNEmbedding, normalize_faces
from opencv_facerecognizer_tpu.models.model import PredictableModel
from opencv_facerecognizer_tpu.ops import image as image_ops
from opencv_facerecognizer_tpu.ops.distance import CosineDistance
from opencv_facerecognizer_tpu.parallel import make_mesh
from opencv_facerecognizer_tpu.parallel import pipeline as pipeline_mod
from opencv_facerecognizer_tpu.runtime import FakeConnector, RecognizerService
from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC, RESULT_TOPIC
from opencv_facerecognizer_tpu.utils import serialization
from opencv_facerecognizer_tpu.utils.dataset import (
    make_synthetic_faces, make_synthetic_scenes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FRAME = (64, 96)
FACE = (16, 16)
SMALL = dict(stem_features=(4, 4, 8), stage_features=(8, 12, 12, 16),
             stage_blocks=(1, 2, 1, 1), neck_features=8, head_features=8,
             head_convs=2, head_groups=2)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(REPO, "benchmark", "configs",
                        "watchlist4m-scrfd-r50_reference.py")
    spec = importlib.util.spec_from_file_location("scrfd_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def small():
    """(detector of small widths with seeded parameters, frames)."""
    det = scrfd.SCRFDDetector(max_faces=3, **SMALL)
    det.load_params(scrfd.random_params(det.net, FRAME, seed=7))
    frames = np.random.default_rng(11).uniform(0, 255, (3, *FRAME)).astype(np.float32)
    return det, frames


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def test_no_norm_statistic_is_left_at_its_default(small):
    det, _ = small
    flat = jax.tree_util.tree_flatten_with_path(det.params)[0]
    by_name = {}
    for path, leaf in flat:
        by_name.setdefault(path[-1].key, []).append(np.asarray(leaf))
    assert all(np.abs(m).max() > 1e-3 for m in by_name["mean"])
    assert all(np.abs(v - 1.0).max() > 1e-3 for v in by_name["var"])
    assert all(np.abs(s - 1.0).max() > 0.05 for s in by_name["scale"])
    # stem 3, two a block and one a shortcut of stages 2-4
    assert len(by_name["mean"]) == 3 + 2 * 5 + 3
    assert sorted(k for k in det.params if k.startswith("head_scale")) == [
        "head_scale0", "head_scale1", "head_scale2"]


@pytest.mark.parametrize("dtype,tol,why", [
    # float32 operands on both sides: what is left is the order of the sums
    (jnp.float32, 2e-4, "rounding order only"),
    # bf16 operands carry 8 bits through 12 convolutions and the two norms
    (jnp.bfloat16, 0.08, "bf16 operands, f32 accumulation"),
])
def test_flax_net_agrees_with_the_plain_reference_at_every_level(
        small, reference, dtype, tol, why):
    det, frames = small
    net = det.net.clone(dtype=dtype)
    ours = net.apply({"params": det.params}, jnp.asarray(frames))
    cls, reg = reference.scrfd_forward(_f32(det.params), det.config(),
                                       det.net.eps, jnp.asarray(frames))
    assert [c.shape for c in ours["cls"]] == [(3, 8, 12, 2), (3, 4, 6, 2), (3, 2, 3, 2)]
    assert [r.shape for r in ours["reg"]] == [(3, 8, 12, 2, 4), (3, 4, 6, 2, 4),
                                              (3, 2, 3, 2, 4)]
    for level in range(3):
        for a, b in ((ours["cls"][level], cls[level]), (ours["reg"][level], reg[level])):
            a, b = np.asarray(a, np.float32), np.asarray(b)
            assert a.shape == b.shape and a.dtype == np.float32
            spread = np.abs(b).mean() + b.std()
            assert np.abs(a - b).max() < tol * max(spread, 1.0), (why, level)
    # the control one step lower is far outside the bf16 tolerance
    low, _ = reference.scrfd_forward(_f32(det.params), det.config(), det.net.eps,
                                     jnp.asarray(frames), reference.fp8)
    assert max(np.abs(np.asarray(low[lv]) - np.asarray(cls[lv])).max()
               for lv in range(3)) > 0.08


def _planted(reference, boxes_and_logits, frame=FRAME, anchors=2):
    """Outputs of one frame in which every anchor is quiet (logit -9, unit
    distances) but the planted ones: [(level, y, x, anchor, logit, (l, t,
    r, b))]."""
    cls = [np.full((1, frame[0] // s, frame[1] // s, anchors), -9.0, np.float32)
           for s in scrfd.STRIDES]
    reg = [np.ones((1, frame[0] // s, frame[1] // s, anchors, 4), np.float32)
           for s in scrfd.STRIDES]
    for level, y, x, a, logit, dist in boxes_and_logits:
        cls[level][0, y, x, a] = logit
        reg[level][0, y, x, a] = dist
    return {"cls": tuple(jnp.asarray(c) for c in cls),
            "reg": tuple(jnp.asarray(r) for r in reg)}


PLANTS = {
    # two anchors of one cell on one face (IoU 0.68 > 0.4): the better one stays
    "overlapping_pair": [(0, 4, 6, 0, 2.0, (2.0, 2.0, 2.0, 2.0)),
                         (0, 4, 6, 1, 1.0, (2.5, 2.0, 2.0, 2.5)),
                         (1, 1, 1, 0, 0.5, (0.9, 0.9, 0.9, 0.9))],
    # sigmoid(-0.01) = 0.4975: under the threshold 0.5, sigmoid(0.01) over it
    "wrong_side_of_threshold": [(0, 2, 2, 0, -0.01, (1.0, 1.0, 1.0, 1.0)),
                                (0, 6, 9, 1, 0.01, (1.0, 1.0, 1.0, 1.0))],
    # five faces that do not touch, three slots: the three best, best first
    "more_than_max_faces": [(0, 1, 1 + 2 * i, 0, 1.0 + 0.3 * i, (0.9, 0.9, 0.9, 0.9))
                            for i in range(5)],
    # a box over the frame's edge is clamped; a coarse level's stride is 32
    "clamped_at_the_edge": [(2, 0, 0, 1, 3.0, (1.0, 1.0, 1.0, 1.0)),
                            (2, 1, 2, 0, 2.0, (0.5, 0.5, 1.5, 1.5))],
}
KEPT = {"overlapping_pair": 2, "wrong_side_of_threshold": 1, "more_than_max_faces": 3,
        "clamped_at_the_edge": 2}


@pytest.mark.parametrize("name", sorted(PLANTS))
def test_decode_against_a_numpy_decode_on_planted_outputs(reference, name):
    outputs = _planted(reference, PLANTS[name])
    cfg = {"max_faces": 3, "num_anchors": 2, "pre_nms": 12, "score_threshold": 0.5,
           "iou_threshold": 0.4}
    boxes, scores, valid = (np.asarray(v) for v in scrfd.decode(
        outputs, FRAME, 3, 0.5, 0.4, pre_nms=12))
    want = reference.decode([np.asarray(c[0]) for c in outputs["cls"]],
                            [np.asarray(r[0]) for r in outputs["reg"]], cfg, FRAME)
    assert valid[0].sum() == want[2].sum() == KEPT[name]
    np.testing.assert_array_equal(valid[0], want[2])
    np.testing.assert_allclose(boxes[0], want[0], atol=1e-4)
    np.testing.assert_allclose(scores[0][valid[0]], want[1][want[2]], atol=1e-6)
    assert np.all(np.diff(scores[0][valid[0]]) <= 0)  # best first
    assert (boxes[0] >= 0).all() and (boxes[0][:, 2] <= FRAME[0]).all() \
        and (boxes[0][:, 3] <= FRAME[1]).all()
    if name == "overlapping_pair":
        np.testing.assert_allclose(boxes[0][0], [16.0, 32.0, 48.0, 64.0])  # yxyx, stride 8
    if name == "clamped_at_the_edge":
        np.testing.assert_allclose(boxes[0][0], [0.0, 0.0, 32.0, 32.0])


def test_decode_of_the_net_agrees_with_the_reference(small, reference):
    """Whole detector, float32 net: the same faces in the same order."""
    det, frames = small
    net = det.net.clone(dtype=jnp.float32)
    outputs = net.apply({"params": det.params}, jnp.asarray(frames))
    boxes, scores, valid = (np.asarray(v) for v in scrfd.decode(
        outputs, FRAME, det.max_faces, det.score_threshold, det.iou_threshold,
        det.pre_nms))
    ref = reference.Reference.__new__(reference.Reference)
    ref.nets = {"detector": _f32(det.params), "detector_cfg": det.config()}
    ref._detect = jax.jit(lambda p, f: reference.scrfd_forward(
        p, det.config(), det.net.eps, f))
    want_boxes, want_scores, want_valid = ref.detect(frames)
    assert valid.sum() >= 3
    np.testing.assert_array_equal(valid, want_valid)
    np.testing.assert_allclose(boxes, want_boxes, atol=2e-2)
    np.testing.assert_allclose(scores[valid], want_scores[want_valid], atol=1e-4)


def test_counts_at_the_published_sizes():
    from benchmark.readers import scrfd_cost

    net = scrfd.SCRFDNet()
    with open(os.path.join(REPO, "benchmark", "configs",
                           "watchlist4m-scrfd-r50.json")) as fh:
        spec = json.load(fh)["detector"]
    macs = scrfd.multiply_adds(net, scrfd.VGA)
    assert macs == scrfd_cost.multiply_adds(spec) == spec["multiply_adds_per_frame"]
    assert macs == 9_914_793_600 and abs(macs / 9.98e9 - 1) < 0.01
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *scrfd.VGA)))["params"]
    count = scrfd.parameter_count(shapes)
    assert count == spec["parameters"] == 3_860_225 and round(count / 1e6, 2) == 3.86
    out = jax.eval_shape(lambda p, x: net.apply({"params": p}, x), shapes,
                         jnp.zeros((2, *scrfd.VGA)))
    assert [c.shape for c in out["cls"]] == [(2, 60, 80, 2), (2, 30, 40, 2), (2, 15, 20, 2)]
    assert all(c.dtype == jnp.float32 for c in out["cls"] + out["reg"])
    assert len(scrfd.anchor_grid(scrfd.VGA)[0]) == spec["anchors"]["per_frame"] == 12_600
    assert [len([k for k in shapes if k.startswith(f"stage{s}_")])
            for s in (1, 2, 3, 4)] == spec["stage_blocks"] == [3, 4, 2, 3]
    # one head a stride would be the 4.19 M the published count rules out
    apart = jax.eval_shape(scrfd.SCRFDNet(strides_share=False).init,
                           jax.random.PRNGKey(0), jnp.zeros((1, *scrfd.VGA)))["params"]
    assert scrfd.parameter_count(apart) > 4_150_000
    # the small net's count follows the same function as the reader's
    small_net = scrfd.SCRFDNet(**SMALL)
    assert scrfd.multiply_adds(small_net, FRAME) == scrfd_cost.multiply_adds(
        {**{k: list(v) if isinstance(v, tuple) else v for k, v in SMALL.items()},
         "input_size": list(FRAME), "in_channels": 3, "num_anchors": 2})


def test_atss_gives_every_face_positives_inside_it():
    boxes = np.array([[4, 6, 30, 32], [34, 50, 62, 78], [8, 60, 22, 74]], np.float32)
    assigned = scrfd.atss_assign(FRAME, boxes, 3)
    centres, _strides, _sides = scrfd.anchor_grid(FRAME)
    assert assigned.shape == (len(centres),) and assigned.max() == 2
    for g, box in enumerate(boxes):
        mine = centres[assigned == g]
        assert 1 <= len(mine) <= 27
        assert ((mine[:, 0] > box[0]) & (mine[:, 0] < box[2])
                & (mine[:, 1] > box[1]) & (mine[:, 1] < box[3])).all()
    assert (scrfd.atss_assign(FRAME, boxes, 0) == -1).all()
    pos, target = scrfd.scrfd_targets(FRAME, boxes[None], np.array([3]))
    assert pos.sum() == (assigned >= 0).sum() and (target[0][~pos[0]] == 0).all()


def test_a_few_training_steps_lower_the_loss():
    scenes, boxes, counts = make_synthetic_scenes(16, FRAME, max_faces=2, seed=47,
                                                  face_size_range=(14, 36))
    det = scrfd.SCRFDDetector(max_faces=2, **SMALL)
    losses = []
    det.train(scenes, boxes, counts, steps=40, batch_size=8, learning_rate=3e-3,
              seed=1, losses=losses, calibration_frames=8)
    losses = np.asarray([float(v) for v in losses])
    assert np.isfinite(losses).all()
    assert losses[-5:].mean() < 0.7 * losses[:5].mean()
    # the calibration pass stored moments: inference no longer reads (0, 1)
    flat = jax.tree_util.tree_flatten_with_path(det.params)[0]
    means = [np.asarray(leaf) for path, leaf in flat if path[-1].key == "mean"]
    assert all(np.abs(m).max() > 1e-4 for m in means)
    boxes_out, _scores, valid = det.detect_batch(scenes[:2])
    assert np.isfinite(np.asarray(boxes_out)).all() and valid.shape == (2, 2)


# ---- checkpoints and the serving app ----


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory, small):
    """A gallery directory, one checkpoint of each detector class, a gate
    and a small embedder, as ``_load_stack`` reads them."""
    import cv2

    tmp = tmp_path_factory.mktemp("scrfd_artifacts")
    faces, y, names = make_synthetic_faces(3, 4, FACE, seed=43, noise=8.0)
    gallery_dir = tmp / "gallery"
    for image, label in zip(np.asarray(faces), y):
        os.makedirs(gallery_dir / names[label], exist_ok=True)
        n = len(os.listdir(gallery_dir / names[label]))
        cv2.imwrite(str(gallery_dir / names[label] / f"{n}.png"),
                    np.clip(image, 0, 255).astype(np.uint8))
    scenes, boxes, counts = make_synthetic_scenes(16, FRAME, max_faces=2, seed=47,
                                                  face_size_range=(14, 36))
    det, _ = small
    det.save(str(tmp / "scrfd.ckpt"))
    heat = CNNFaceDetector(features=(4, 8), head_features=8, max_faces=3,
                           score_threshold=0.02, space_to_depth=4)
    heat.load_params(jax.jit(heat.net.init)(
        jax.random.PRNGKey(3), jnp.zeros((1, *FRAME)))["params"])
    heat.save(str(tmp / "heatmap.ckpt"))
    FaceGate().train(scenes, boxes, counts, steps=10).save(str(tmp / "cascade.ckpt"))
    cnn = CNNEmbedding(embed_dim=16, input_size=FACE, stem_features=4,
                       stage_features=(4, 8), stage_blocks=(1, 1), train_steps=2)
    cnn.compute(np.asarray(faces, np.float32), y)
    serialization.save_model(str(tmp / "cnn.ckpt"), PredictableModel(
        cnn, NearestNeighbor(CosineDistance())))
    return {"dir": str(tmp), "gallery": str(gallery_dir), "scenes": scenes,
            "names": names}


def _args(artifacts, detector):
    return recognize_app.build_parser().parse_args([
        "--model", os.path.join(artifacts["dir"], "cnn.ckpt"),
        "--detector", os.path.join(artifacts["dir"], detector),
        "--cascade", os.path.join(artifacts["dir"], "cascade.ckpt"),
        "--gallery", artifacts["gallery"], "--source", "dir",
        "--frame-size", str(FRAME[0]), str(FRAME[1]), "--capacity", "64"])


def test_checkpoint_round_trip_by_the_header(artifacts, small):
    det, frames = small
    path = os.path.join(artifacts["dir"], "scrfd.ckpt")
    loaded = scrfd.load_detector(path)
    assert isinstance(loaded, scrfd.SCRFDDetector) and loaded.kind == "scrfd"
    assert loaded.config() == det.config()
    same = jax.tree_util.tree_map(lambda a, b: bool(np.array_equal(a, b)),
                                  det.params, loaded.params)
    assert all(jax.tree_util.tree_leaves(same))
    for ours, theirs in zip(loaded.detect_batch(frames), det.detect_batch(frames)):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    # a file whose header names no kind is the heat-map detector's
    heat = scrfd.load_detector(os.path.join(artifacts["dir"], "heatmap.ckpt"))
    assert isinstance(heat, CNNFaceDetector)
    with pytest.raises(ValueError, match="not an SCRFD checkpoint"):
        scrfd.SCRFDDetector.load(os.path.join(artifacts["dir"], "heatmap.ckpt"))
    # box-shaped results for one image, as CNNFaceDetector.detect gives them
    found = loaded.detect(frames[0])
    assert found and all(len(b) == 4 and all(isinstance(v, int) for v in b) for b in found)


@pytest.mark.parametrize("ckpt,cls_name,kind,decode_scope", [
    ("scrfd.ckpt", "SCRFDDetector", "scrfd", True),
    ("heatmap.ckpt", "CNNFaceDetector", "heatmap", False)])
def test_the_fused_step_gives_what_the_stages_give_one_by_one(
        artifacts, ckpt, cls_name, kind, decode_scope):
    """``_load_stack`` tells the class by the header; the step reaches the
    detector only through ``as_detector``: its boxes are ``detect_batch``'s,
    its labels and similarities those of crop -> embed -> match on them."""
    mesh = make_mesh(devices=jax.devices()[:1])
    pipeline, names = recognize_app._load_stack(_args(artifacts, ckpt), mesh=mesh)
    detector = pipeline.detector
    assert type(detector).__name__ == cls_name
    assert pipeline_mod.as_detector(detector).kind == kind
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(40, 16)).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=-1, keepdims=True)
    pipeline.gallery.add(rows, 100 + np.arange(40, dtype=np.int32))
    frames = np.floor(artifacts["scenes"][:4]).astype(np.float32)
    result = pipeline.recognize_batch(frames)
    boxes, scores, valid = detector.detect_batch(frames)
    assert np.asarray(valid).sum() >= 4, "nothing to compare"
    np.testing.assert_array_equal(np.asarray(result.valid), np.asarray(valid))
    np.testing.assert_allclose(np.asarray(result.boxes), np.asarray(boxes), atol=1e-3)
    np.testing.assert_allclose(np.asarray(result.det_scores)[np.asarray(valid)],
                               np.asarray(scores)[np.asarray(valid)], atol=1e-5)
    crops = image_ops.batched_crop_resize(jnp.asarray(frames), result.boxes, FACE)
    flat = normalize_faces(crops.reshape((-1, *FACE)), FACE)
    emb = np.asarray(pipeline.embed_net.apply({"params": pipeline.embed_params}, flat))
    data = pipeline.gallery.data
    stored = np.asarray(data.embeddings[:data.size], np.float32)
    labels = np.asarray(data.labels[:data.size])
    sims = emb @ stored.T
    # (a seeded detector's box may be a sliver whose crop is one flat patch:
    # standardizing it amplifies the last bit, so those slots are left out)
    sides = np.asarray(result.boxes).reshape(-1, 4)
    roomy = np.minimum(sides[:, 2] - sides[:, 0], sides[:, 3] - sides[:, 1]) >= 6
    ok = np.asarray(valid).reshape(-1) & roomy
    assert ok.sum() >= (3 if kind == "scrfd" else 0)  # the untrained heat-map net's are all slivers
    np.testing.assert_allclose(np.asarray(result.similarities).reshape(-1)[ok],
                               sims.max(axis=1)[ok], atol=2e-2)  # bf16 nets, two graphs
    # top-1: the same row, or a row the stages hold within the tolerance of it
    served = np.asarray(result.labels).reshape(-1)[ok]
    at_served = np.array([sims[i, labels == lab].max()
                          for i, lab in zip(np.flatnonzero(ok), served)])
    same = served == labels[sims.argmax(axis=1)][ok]
    assert (not ok.any() or same.mean() >= 0.75) and np.all(sims.max(axis=1)[ok] - at_served < 2e-2)
    # the packed step: the same answer, the dispatch's provenance, the scopes
    packed = np.asarray(pipeline.recognize_batch_packed(frames.astype(np.uint8)))
    out = pipeline_mod.unpack_result(packed, 1)
    np.testing.assert_array_equal(out.valid, np.asarray(valid))
    info = pipeline.last_dispatch_info
    assert (info["detector"], info["detect_frames"], info["embed_slots"]) == (
        kind, 4, 4 * detector.max_faces)
    text = pipeline.lower_packed(4, *FRAME, np.uint8).as_text(debug_info=True)
    assert "ocvf_detect" in text and "ocvf_crop" in text
    assert ("ocvf_decode" in text) is decode_scope


def test_detect_frames_counts_the_rung_s_frames(artifacts):
    """3 frames dispatch at the 4 rung, 7 at the 8 rung: 12 frames through
    the detector, whatever they hold; the batch span names its kind."""
    from opencv_facerecognizer_tpu.utils.tracing import BATCH_TOPIC, Tracer

    mesh = make_mesh(devices=jax.devices()[:1])
    pipeline, _names = recognize_app._load_stack(_args(artifacts, "scrfd.ckpt"), mesh=mesh)
    connector = FakeConnector()
    tracer = Tracer(ring_size=256, sample=1.0, seed=0)
    service = RecognizerService(pipeline, connector, batch_size=8,
                                bucket_sizes=(4, 8), frame_shape=FRAME,
                                flush_timeout=0.05, similarity_threshold=0.0,
                                tracer=tracer)
    service.start(warmup=False)
    try:
        sent = 0
        for burst in (3, 7):
            for _ in range(burst):
                connector.inject(FRAME_TOPIC, {"frame": np.zeros(FRAME, np.float32),
                                               "meta": {"i": sent}})
                sent += 1
            deadline = time.monotonic() + 120
            while (len(connector.messages(RESULT_TOPIC)) < sent
                   and time.monotonic() < deadline):
                time.sleep(0.01)
    finally:
        assert service.drain(timeout=60.0)
        service.stop()
    assert service.metrics.counter("batches_dispatched") == 2
    assert service.metrics.counter("detect_frames") == 4 + 8
    assert service.metrics.counter("embed_slots") == (4 + 8) * 3
    kinds = {s.get("detector") for s in tracer.snapshot(BATCH_TOPIC)
             if s["stage"] == "dispatch"}
    assert kinds == {"scrfd"}

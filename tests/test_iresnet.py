"""The IResNet embedder: the flax net against the benchmark's plain reference
(``benchmark/configs/watchlist4m-r50_reference.py``, float32 at highest
precision, no flax), its counts at the published sizes, its checkpoint
through ``serialization`` and ``_load_stack`` beside ``CNNEmbedding``, one
fused step through ``RecognitionPipeline`` against the reference, the
streaming matcher at D = 512, and the ``embed_slots`` counter. Everything on
seeded random weights with no BatchNorm statistic or PReLU slope at its
default, at CPU size."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.apps import recognize as recognize_app
from opencv_facerecognizer_tpu.models import iresnet
from opencv_facerecognizer_tpu.models.cascade import FaceGate
from opencv_facerecognizer_tpu.models.classifier import NearestNeighbor
from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
from opencv_facerecognizer_tpu.models.embedder import CNNEmbedding, normalize_faces
from opencv_facerecognizer_tpu.models.model import PredictableModel
from opencv_facerecognizer_tpu.ops.distance import CosineDistance
from opencv_facerecognizer_tpu.ops.pallas_match import streaming_match_topk
from opencv_facerecognizer_tpu.parallel import make_mesh
from opencv_facerecognizer_tpu.runtime import FakeConnector, RecognizerService
from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC, RESULT_TOPIC
from opencv_facerecognizer_tpu.utils import serialization
from opencv_facerecognizer_tpu.utils.dataset import (
    make_synthetic_faces, make_synthetic_scenes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FACE = (32, 32)
SMALL = dict(embed_dim=32, stem_features=8, stage_features=(8, 16, 32, 64),
             stage_blocks=(1, 1, 2, 1))


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(REPO, "benchmark", "configs", "watchlist4m-r50_reference.py")
    spec = importlib.util.spec_from_file_location("r50_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def small():
    """(feature with seeded, calibrated parameters; the faces it saw)."""
    faces, _y, _names = make_synthetic_faces(6, 8, FACE, seed=41, noise=8.0)
    feature = iresnet.IResNetEmbedding(input_size=FACE, seed=5, **SMALL)
    feature.compute(np.asarray(faces, np.float32))
    return feature, np.asarray(faces, np.float32)


def _leaves(params, name):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return [np.asarray(leaf) for path, leaf in flat if path[-1].key == name]


def test_no_statistic_or_slope_is_left_at_its_default(small):
    feature, _ = small
    net = feature._params["net"]
    assert all(np.abs(m).max() > 1e-3 for m in _leaves(net, "mean"))
    assert all(np.abs(v - 1.0).max() > 1e-3 for v in _leaves(net, "var"))
    assert all(np.abs(s - 0.25).min() > 0 for s in _leaves(net, "slope"))
    assert all(np.abs(s - 1.0).max() > 0.1 for s in _leaves(net, "scale"))
    assert len(_leaves(net, "mean")) == 1 + 5 * 3 + 4 + 2  # stem, blocks, shortcuts, head


@pytest.mark.parametrize("dtype,floor,why", [
    # float32 operands on both sides: what is left is the order of the sums
    # (XLA's convolution against the reference's HIGHEST-precision one) and
    # rsqrt against 1/sqrt, through 11 convolutions
    (jnp.float32, 0.999999, "rounding order only"),
    # bf16 operands carry 8 bits: 2^-9 relative a product, averaged over a
    # convolution's taps and compounded over 11 layers it stays under a
    # percent of the embedding's norm; 0.999 is a tenth of what fp8 reads
    (jnp.bfloat16, 0.999, "bf16 operands, f32 accumulation"),
])
def test_flax_net_agrees_with_the_plain_reference(small, reference, dtype, floor, why):
    feature, faces = small
    x = normalize_faces(faces, FACE)
    net = iresnet.IResNet(dtype=dtype, **SMALL)
    ours = np.asarray(net.apply({"params": feature._params["net"]}, x))
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    feature._params["net"])
    theirs = np.asarray(reference.embedder_forward(params, feature.eps, x))
    cos = np.sum(ours * theirs, axis=-1)
    assert cos.min() > floor, (why, cos.min())
    np.testing.assert_allclose(np.linalg.norm(ours, axis=-1), 1.0, atol=1e-5)
    # the control one step lower is far outside either tolerance
    low = np.asarray(reference.embedder_forward(params, feature.eps, x,
                                                reference.fp8))
    assert np.sum(low * theirs, axis=-1).min() < 0.99


def test_calibration_is_the_pass_it_stores(small):
    """Inference with the stored moments repeats the calibration pass, and
    embeddings of different faces do not collapse onto one direction."""
    feature, faces = small
    again = np.asarray(feature.extract(faces))
    fresh = iresnet.IResNetEmbedding(input_size=FACE, seed=5, **SMALL)
    first = np.asarray(fresh.compute(faces))
    np.testing.assert_allclose(again, first, atol=2e-2)  # bf16 activations, two graphs
    sims = again @ again.T
    assert np.abs(sims[~np.eye(len(sims), dtype=bool)].mean()) < 0.1


def test_counts_at_the_published_sizes():
    net = iresnet.IResNet()
    macs = iresnet.multiply_adds(net, iresnet.R50_FACE_SIZE)
    assert macs == 6_309_330_944 and round(macs / 1e9, 2) == 6.31
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *iresnet.R50_FACE_SIZE)))["params"]
    count = iresnet.parameter_count(shapes)
    assert count == 43_590_848 and round(count / 1e6, 1) == 43.6
    out = jax.eval_shape(lambda p, x: net.apply({"params": p}, x), shapes,
                         jnp.zeros((2, *iresnet.R50_FACE_SIZE)))
    assert out.shape == (2, 512) and out.dtype == jnp.float32
    assert shapes["fc_kernel"].shape == (25088, 512)
    assert [len([k for k in shapes if k.startswith(f"stage{s}_")])
            for s in (1, 2, 3, 4)] == [3, 4, 14, 3]


# ---- checkpoints and the serving app ----


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory, small):
    """A gallery directory, a detector, a gate and one checkpoint of each
    embedder class, all in one directory as the reference reads them."""
    import cv2

    tmp = tmp_path_factory.mktemp("iresnet_artifacts")
    faces, y, names = make_synthetic_faces(3, 4, FACE, seed=43, noise=8.0)
    gallery_dir = tmp / "gallery"
    for image, label in zip(np.asarray(faces), y):
        os.makedirs(gallery_dir / names[label], exist_ok=True)
        n = len(os.listdir(gallery_dir / names[label]))
        cv2.imwrite(str(gallery_dir / names[label] / f"{n}.png"),
                    np.clip(image, 0, 255).astype(np.uint8))
    scenes, boxes, counts = make_synthetic_scenes(48, (96, 96), max_faces=2, seed=47)
    det = CNNFaceDetector(features=(8, 16, 32), head_features=32, max_faces=2,
                          score_threshold=0.25)
    det.train(scenes, boxes, counts, steps=200, batch_size=16, learning_rate=2e-3)
    det.save(str(tmp / "detector.ckpt"))
    FaceGate().train(scenes, boxes, counts, steps=20).save(str(tmp / "cascade.ckpt"))
    feature, _ = small
    serialization.save_model(str(tmp / "embedder.ckpt"), PredictableModel(
        feature, NearestNeighbor(CosineDistance())))
    cnn = CNNEmbedding(embed_dim=16, input_size=FACE, stem_features=4,
                       stage_features=(4, 8), stage_blocks=(1, 1), train_steps=2)
    cnn.compute(np.asarray(faces, np.float32), y)
    serialization.save_model(str(tmp / "cnn.ckpt"), PredictableModel(
        cnn, NearestNeighbor(CosineDistance())))
    return {"dir": str(tmp), "gallery": str(gallery_dir), "scenes": scenes,
            "names": names}


def _args(artifacts, model, *extra):
    return recognize_app.build_parser().parse_args([
        "--model", os.path.join(artifacts["dir"], model),
        "--detector", os.path.join(artifacts["dir"], "detector.ckpt"),
        "--cascade", os.path.join(artifacts["dir"], "cascade.ckpt"),
        "--gallery", artifacts["gallery"], "--source", "dir",
        "--frame-size", "96", "96", "--capacity", "64", *extra])


def test_checkpoint_round_trip_through_serialization(artifacts, small):
    feature, faces = small
    model = serialization.load_model(os.path.join(artifacts["dir"], "embedder.ckpt"))
    loaded = model.feature
    assert isinstance(loaded, iresnet.IResNetEmbedding)
    assert loaded.get_config() == feature.get_config()
    same = jax.tree_util.tree_map(lambda a, b: bool(np.array_equal(a, b)),
                                  feature._params["net"], loaded._params["net"])
    assert all(jax.tree_util.tree_leaves(same))
    np.testing.assert_array_equal(np.asarray(loaded.extract(faces)),
                                  np.asarray(feature.extract(faces)))


@pytest.mark.parametrize("model,net_class,dim", [
    ("embedder.ckpt", "IResNet", 32), ("cnn.ckpt", "FaceEmbedNet", 16)])
def test_load_stack_serves_either_feature_class(artifacts, model, net_class, dim):
    mesh = make_mesh(devices=jax.devices()[:1])
    pipeline, names = recognize_app._load_stack(_args(artifacts, model), mesh=mesh)
    assert type(pipeline.embed_net).__name__ == net_class
    assert pipeline.face_size == FACE and pipeline.gallery.dim == dim
    assert sorted(names) == sorted(artifacts["names"])
    packed = np.asarray(pipeline.recognize_batch_packed(
        artifacts["scenes"][:2].astype(np.uint8)))
    # boxes, label, similarity (an empty slot's score is -inf)
    from opencv_facerecognizer_tpu.parallel.pipeline import unpack_result

    out = unpack_result(packed, 1)
    assert packed.shape == (2, 2, 8) and packed.dtype == np.int32
    assert np.isfinite(out.boxes).all() and np.isfinite(out.similarities).all()
    assert ((out.labels >= -1) & (out.labels < len(names))).all()
    assert pipeline.last_dispatch_info["embed_slots"] == 2 * 2


def test_a_checkpoint_of_neither_class_is_refused(artifacts, tmp_path):
    from opencv_facerecognizer_tpu.models.feature import Identity

    path = str(tmp_path / "identity.ckpt")
    serialization.save_model(path, PredictableModel(Identity(), NearestNeighbor()))
    args = _args(artifacts, "embedder.ckpt")
    args.model = path
    with pytest.raises(SystemExit, match="embedder checkpoint"):
        recognize_app._load_stack(args)


def test_one_pipeline_step_against_the_reference(artifacts, reference):
    """Boxes, embeddings and top-1 of the fused step, held against the plain
    reference reading the same three files."""
    mesh = make_mesh(devices=jax.devices()[:1])
    pipeline, _ = recognize_app._load_stack(_args(artifacts, "embedder.ckpt"), mesh=mesh)
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(40, 32)).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=-1, keepdims=True)
    pipeline.gallery.add(rows, 100 + np.arange(40, dtype=np.int32))
    frames = np.floor(artifacts["scenes"][:8]).astype(np.float32)
    result = pipeline.recognize_batch(frames)
    ref = reference.Reference(artifacts["dir"], FACE)
    boxes, scores, valid = ref.detect(frames)
    ours_valid = np.asarray(result.valid)
    assert ours_valid.sum() >= 4, "the tiny detector found too little to compare"
    # slots are ordered by score on both sides; a bf16 heatmap peak may fall
    # in the next cell, so hold the median box to half a pixel and most slots
    agree = ours_valid & valid
    gap = np.abs(np.asarray(result.boxes) - boxes).max(axis=-1)[agree]
    assert agree.sum() >= 0.75 * ours_valid.sum()
    assert np.median(gap) < 0.5, gap
    # the embedder, on the boxes the program served: bf16 against f32
    # through 11 convolutions (0.999 as in the net's own test), and the crop
    emb = ref.embed(frames, np.asarray(result.boxes))
    data = pipeline.gallery.data
    stored = np.asarray(data.embeddings[:data.size], np.float32)
    labels = np.asarray(data.labels[:data.size])
    sims = emb.reshape(-1, 32) @ stored.T
    best, top1 = sims.max(axis=1), labels[sims.argmax(axis=1)]
    flat_valid = ours_valid.reshape(-1)
    served_sim = np.asarray(result.similarities).reshape(-1)[flat_valid]
    served_lab = np.asarray(result.labels).reshape(-1)[flat_valid]
    np.testing.assert_allclose(served_sim, best[flat_valid], atol=0.02)
    # top-1: equal, or a near-tie between two rows the reference holds equal
    runner = np.sort(sims, axis=1)[:, -2][flat_valid]
    same = served_lab == top1[flat_valid]
    assert np.all(same | (best[flat_valid] - runner < 0.02)) and same.mean() >= 0.75


# ---- the matcher at D = 512 ----


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


@pytest.mark.parametrize("qn,n,k", [(64, 6144, 1), (256, 6144, 1),
                                    (1024, 6144, 1), (64, 3001, 5)])
def test_streaming_matcher_at_d512_equals_an_xla_argmax(qn, n, k):
    """Every ladder rung over three gallery tiles of D = 512 (``_plan``
    gives 2,048-row tiles there), and an IVF-like bucket (k = 5, N no
    multiple of 128), in interpret mode against the arg-max of the same
    bf16-rounded operands; the plan has no constant of its own for 512."""
    from opencv_facerecognizer_tpu.ops import pallas_match

    assert pallas_match._plan(1024, 4194304, 512, 1, 2, None, None) == (1024, 2048, 1024, 1024)
    rng = np.random.default_rng(qn + n)
    q = rng.normal(size=(qn, 512)).astype(np.float32)
    g = rng.normal(size=(n, 512)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    g /= np.linalg.norm(g, axis=-1, keepdims=True)
    valid = rng.random(n) > 0.05
    vals, idx = (np.asarray(v) for v in streaming_match_topk(
        jnp.asarray(q), jnp.asarray(g).astype(jnp.bfloat16), jnp.asarray(valid),
        k=k, interpret=True))
    sims = np.where(valid[None, :], _bf16(q) @ _bf16(g).T, -1e30)
    order = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    np.testing.assert_allclose(vals, np.take_along_axis(sims, order, axis=1), atol=1e-5)
    served = np.take_along_axis(sims, idx, axis=1)
    np.testing.assert_allclose(served, vals, atol=1e-5)  # the row served scores what is served
    assert (idx == order).mean() > 0.999 and valid[idx].all()


# ---- the counter ----


def test_embed_slots_counts_rung_frames_times_face_slots():
    """3 frames dispatch at the 4 rung, 7 at the 8 rung: 2 face slots a
    frame, so 8 + 16 slots, whatever the frames hold."""
    from scripts.chaos_soak import build_stack

    pipeline, _mesh = build_stack(frame_shape=(32, 32), face=(16, 16))
    connector = FakeConnector()
    service = RecognizerService(pipeline, connector, batch_size=8,
                                bucket_sizes=(4, 8), frame_shape=(32, 32),
                                flush_timeout=0.05, similarity_threshold=0.0)
    service.start(warmup=False)
    try:
        sent = 0
        for burst in (3, 7):
            for _ in range(burst):
                connector.inject(FRAME_TOPIC, {"frame": np.zeros((32, 32), np.float32),
                                               "meta": {"i": sent}})
                sent += 1
            deadline = time.monotonic() + 60
            while (len(connector.messages(RESULT_TOPIC)) < sent
                   and time.monotonic() < deadline):
                time.sleep(0.01)
    finally:
        assert service.drain(timeout=30.0)
        service.stop()
    assert service.metrics.counter("batches_dispatched") == 2
    assert service.metrics.counter("embed_slots") == (4 + 8) * 2

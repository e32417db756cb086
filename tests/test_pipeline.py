"""End-to-end fused detect->align->embed->match pipeline on the 8-device
CPU mesh (SURVEY.md §3.3 rebuild contract, §7.7)."""

import numpy as np
import pytest

from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
from opencv_facerecognizer_tpu.models.embedder import (
    FaceEmbedNet,
    init_embedder,
    normalize_faces,
    train_embedder,
)
from opencv_facerecognizer_tpu.models.iresnet import IResNetEmbedding
from opencv_facerecognizer_tpu.ops import image as image_ops
from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes


FACE = (32, 32)


@pytest.fixture(scope="module")
def pipeline_setup():
    # Train a tiny detector on synthetic scenes.
    scenes, boxes, counts = make_synthetic_scenes(48, (96, 96), max_faces=2, seed=31)
    det = CNNFaceDetector(features=(8, 16, 32), head_features=32, max_faces=4,
                          score_threshold=0.25)
    det.train(scenes, boxes, counts, steps=250, batch_size=16, learning_rate=2e-3)

    # "Subjects": crops of distinct synthetic faces; embedder trained on them.
    net = FaceEmbedNet(embed_dim=32, stem_features=8, stage_features=(8, 16),
                       stage_blocks=(1, 1))
    crops, labels = [], []
    for i in range(len(scenes)):
        for b in range(counts[i]):
            y0, x0, y1, x1 = boxes[i, b].astype(int)
            crop = np.asarray(image_ops.resize(scenes[i][y0:y1, x0:x1], FACE))
            crops.append(crop)
            labels.append(i % 5)  # 5 pseudo-identities
    crops = np.stack(crops)
    labels = np.asarray(labels, np.int32)
    params = init_embedder(net, num_classes=5, input_shape=FACE, seed=0)
    xn = np.asarray(normalize_faces(crops, FACE))
    params = train_embedder(net, params, xn, labels, steps=40, batch_size=16)
    return det, net, params, scenes, boxes, counts, crops, labels


@pytest.fixture(scope="module")
def embed_nets(pipeline_setup):
    """(net, its parameters) of either feature class the one embed stage
    serves, both 32-d on 32x32 crops: the trained separable net, and the
    tiny IResNet of tests/test_iresnet.py with seeded, calibrated
    parameters."""
    _det, net, params, *_rest, crops, _labels = pipeline_setup
    feature = IResNetEmbedding(input_size=FACE, seed=5, embed_dim=32,
                               stem_features=8,
                               stage_features=(8, 16, 32, 64),
                               stage_blocks=(1, 1, 2, 1))
    feature.compute(np.asarray(crops, np.float32))
    return {"separable": (net, params["net"]),
            "iresnet": (feature.net, feature._params["net"])}


@pytest.mark.parametrize("dp,tp", [(2, 4), (1, 8)])
def test_fused_pipeline_runs_sharded(pipeline_setup, dp, tp):
    det, net, params, scenes, boxes, counts, crops, labels = pipeline_setup
    mesh = make_mesh(dp=dp, tp=tp)
    gallery = ShardedGallery(capacity=64, dim=32, mesh=mesh)
    emb = np.asarray(net.apply({"params": params["net"]},
                               normalize_faces(crops, FACE)))
    gallery.add(emb, labels)

    pipe = RecognitionPipeline(det, net, params["net"], gallery, face_size=FACE, top_k=2)
    batch = scenes[:8]
    result = pipe.recognize_batch(batch)
    assert result.boxes.shape == (8, 4, 4)
    assert result.valid.shape == (8, 4)
    assert result.labels.shape == (8, 4, 2)
    assert result.similarities.shape == (8, 4, 2)
    # detection quality bar (raised from gt//2 per VERDICT round-1 #4):
    # >=90% of ground-truth faces must come out of the fused graph valid.
    det_count = int(np.asarray(result.valid).sum())
    gt_count = int(counts[:8].sum())
    assert det_count >= int(np.ceil(0.9 * gt_count)), (det_count, gt_count)
    # matched labels for valid faces must be real gallery labels
    valid = np.asarray(result.valid)
    lbl = np.asarray(result.labels)[..., 0]
    assert set(np.unique(lbl[valid]).tolist()) <= set(range(5))
    # similarities are cosine-bounded
    sims = np.asarray(result.similarities)[valid]
    assert np.all(sims <= 1.0 + 1e-3)


@pytest.mark.parametrize("feature_class", ["separable", "iresnet"])
def test_pipeline_uint8_transfer_matches_f32(pipeline_setup, embed_nets,
                                             feature_class):
    """The uint8 fast-transfer path (frames ride H2D as uint8, cast to f32
    in-graph) must produce the same result as sending the same pixel
    values as f32 — it is a transfer-format choice, not a model change,
    for either feature class the embed stage serves."""
    det, _net, _params, scenes, boxes, counts, crops, labels = pipeline_setup
    net, net_params = embed_nets[feature_class]
    mesh = make_mesh(tp=8)
    gallery = ShardedGallery(capacity=64, dim=32, mesh=mesh)
    emb = np.asarray(net.apply({"params": net_params},
                               normalize_faces(crops, FACE)))
    gallery.add(emb, labels)
    pipe = RecognitionPipeline(det, net, net_params, gallery,
                               face_size=FACE, top_k=1)
    u8 = np.clip(scenes[:8], 0, 255).astype(np.uint8)
    r_u8 = pipe.recognize_batch(u8)
    r_f32 = pipe.recognize_batch(u8.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(r_u8.valid),
                                  np.asarray(r_f32.valid))
    np.testing.assert_array_equal(np.asarray(r_u8.labels),
                                  np.asarray(r_f32.labels))
    np.testing.assert_allclose(np.asarray(r_u8.boxes),
                               np.asarray(r_f32.boxes), atol=1e-4)
    np.testing.assert_allclose(np.asarray(r_u8.similarities),
                               np.asarray(r_f32.similarities), atol=1e-5)
    # distinct trace per dtype, cached independently
    assert len(pipe._step_cache) == 2


@pytest.mark.parametrize("feature_class", ["separable", "iresnet"])
def test_pipeline_batch_caching(pipeline_setup, embed_nets, feature_class):
    det, _net, _params, scenes, *_ = pipeline_setup
    net, net_params = embed_nets[feature_class]
    mesh = make_mesh(tp=8)
    gallery = ShardedGallery(capacity=16, dim=32, mesh=mesh)
    gallery.add(np.eye(16, 32, dtype=np.float32), np.arange(16, dtype=np.int32))
    pipe = RecognitionPipeline(det, net, net_params, gallery, face_size=FACE)
    r1 = pipe.recognize_batch(scenes[:8])
    assert len(pipe._step_cache) == 1
    r2 = pipe.recognize_batch(scenes[8:16])
    assert len(pipe._step_cache) == 1  # same shape -> no recompile
    pipe.recognize_batch(scenes[:16])
    assert len(pipe._step_cache) == 2

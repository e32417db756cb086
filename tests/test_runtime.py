"""Serving runtime tests: batcher semantics/concurrency (SURVEY.md §5.2),
connectors, the service loop over a fake transport (§5.8), enrolment
protocol, double-buffered reload (§5.3), trainer flows."""

import io
import json
import threading
import time

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime import (
    FakeConnector,
    FrameBatcher,
    JSONLConnector,
    RecognizerService,
    TheTrainer,
)
from opencv_facerecognizer_tpu.runtime.connector import decode_frame, encode_frame
from opencv_facerecognizer_tpu.runtime.recognizer import (
    CONTROL_TOPIC,
    FRAME_TOPIC,
    RESULT_TOPIC,
    STATUS_TOPIC,
)
from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_faces, make_synthetic_scenes

RNG = np.random.default_rng(23)


# ---------- FrameBatcher ----------


def test_batcher_full_batch():
    b = FrameBatcher(batch_size=4, frame_shape=(8, 8), flush_timeout=10.0)
    for i in range(4):
        assert b.put(np.full((8, 8), i, np.float32), meta=i)
    frames, metas, count, _ts, _tids, _pris = b.get_batch()
    assert count == 4 and frames.shape == (4, 8, 8)
    assert metas == [0, 1, 2, 3]
    np.testing.assert_allclose(frames[2], 2.0)


def test_batcher_timeout_flush_pads():
    b = FrameBatcher(batch_size=4, frame_shape=(8, 8), flush_timeout=0.05)
    b.put(np.ones((8, 8), np.float32), meta="only")
    t0 = time.monotonic()
    frames, metas, count, _ts, _tids, _pris = b.get_batch()
    assert time.monotonic() - t0 < 1.0
    assert count == 1
    assert metas[0] == "only" and metas[1] is None
    np.testing.assert_allclose(frames[1], 0.0)


def test_batcher_rejects_malformed():
    b = FrameBatcher(batch_size=2, frame_shape=(8, 8))
    assert not b.put(np.ones((9, 9), np.float32))
    assert not b.put(np.array([["a", "b"]]))
    assert b.stats["dropped_malformed"] == 2


def test_batcher_overflow_drops_oldest():
    b = FrameBatcher(batch_size=2, frame_shape=(4, 4), max_pending=3)
    for i in range(5):
        b.put(np.full((4, 4), i, np.float32), meta=i)
    frames, metas, count, _ts, _tids, _pris = b.get_batch()
    assert b.stats["dropped_overflow"] == 2
    assert metas[:2] == [2, 3]  # oldest (0, 1) dropped


def test_batcher_concurrent_producers_consumer():
    b = FrameBatcher(batch_size=8, frame_shape=(4, 4), flush_timeout=0.02)
    total = 64
    seen = []

    def producer(start):
        for i in range(total // 2):
            b.put(np.zeros((4, 4), np.float32), meta=start + i)
            time.sleep(0.0005)

    threads = [threading.Thread(target=producer, args=(0,)),
               threading.Thread(target=producer, args=(1000,))]
    for t in threads:
        t.start()

    def consumer():
        while len(seen) < total:
            out = b.get_batch(block=True)
            if out is None:
                break
            _, metas, count, _ts, _tids, _pris = out
            seen.extend(metas[:count])

    c = threading.Thread(target=consumer)
    c.start()
    for t in threads:
        t.join()
    c.join(timeout=5.0)
    assert sorted(seen) == sorted(list(range(32)) + list(range(1000, 1032)))


def test_batcher_close_unblocks():
    b = FrameBatcher(batch_size=2, frame_shape=(4, 4))
    done = []

    def consumer():
        done.append(b.get_batch(block=True))

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    b.close()
    t.join(timeout=2.0)
    assert done == [None]


# ---------- continuous batching ----------


def test_batcher_flushes_early_at_size_threshold():
    """A full batch forms the moment batch_size frames are buffered — no
    flush-window wait even with a huge deadline cap."""
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    m = Metrics()
    b = FrameBatcher(batch_size=4, frame_shape=(8, 8), flush_timeout=10.0,
                     metrics=m, target_latency_s=5.0)
    for i in range(4):
        b.put(np.full((8, 8), i, np.float32), meta=i)
    t0 = time.monotonic()
    batch = b.get_batch()
    assert time.monotonic() - t0 < 1.0
    assert batch.count == 4
    assert m.counter("batcher_batches_size") == 1
    assert m.counter("batcher_batches_deadline") == 0
    assert b.stats["batches_size"] == 1


def test_batcher_adaptive_deadline_under_trickle():
    """Under trickle load (fewer than batch_size frames) a batch waits up
    to the ADAPTIVE deadline: target latency minus the reported downstream
    service time, clamped to [min_deadline, flush_timeout] — never the full
    fixed flush window."""
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    m = Metrics()
    b = FrameBatcher(batch_size=8, frame_shape=(4, 4), flush_timeout=5.0,
                     metrics=m, target_latency_s=0.2)
    # No service estimate yet: full budget, capped by flush_timeout.
    assert abs(b.current_flush_deadline() - 0.2) < 1e-9
    b.report_service_time(0.15)  # EWMA seeds at the first report
    assert abs(b.current_flush_deadline() - 0.05) < 1e-6
    # Budget exhausted -> the floor, not zero (back-to-back frames still
    # coalesce) and never a negative wait.
    b.report_service_time(0.5)
    for _ in range(40):
        b.report_service_time(0.5)
    assert b.current_flush_deadline() == b.min_deadline_s
    # The gauge mirrors the current deadline on the shared surface.
    assert m.gauge("batcher_flush_deadline_ms") == b.min_deadline_s * 1e3
    # A trickle frame flushes at ~the deadline, not at flush_timeout.
    b.put(np.zeros((4, 4), np.float32), meta="lone")
    t0 = time.monotonic()
    batch = b.get_batch()
    waited = time.monotonic() - t0
    assert batch.count == 1 and batch.metas[0] == "lone"
    assert waited < 1.0  # far below the 5 s fixed window
    assert m.counter("batcher_batches_deadline") == 1


def test_batcher_coalescing_stats_match_frames_offered():
    """Every offered frame is accounted for on the shared Metrics surface:
    offered == batched + malformed + overflow + closed + still pending."""
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    m = Metrics()
    b = FrameBatcher(batch_size=2, frame_shape=(4, 4), flush_timeout=0.01,
                     max_pending=4, metrics=m)
    b.put(np.ones((9, 9), np.float32))  # malformed
    for i in range(6):  # 4 fit, 2 force overflow drops of the oldest
        b.put(np.full((4, 4), i, np.float32), meta=i)
    batches = []
    while True:
        out = b.get_batch(block=False)
        if out is None:
            break
        batches.append(out)
    b.close()
    b.put(np.zeros((4, 4), np.float32))  # dropped: closed
    batched = sum(bt.count for bt in batches)
    c = m.counters()
    assert c["batcher_frames_offered"] == 8
    assert c["batcher_frames_batched"] == batched == 4
    assert c["batcher_dropped_malformed"] == 1
    assert c["batcher_dropped_overflow"] == 2
    assert c["batcher_dropped_closed"] == 1
    assert b.pending == 0
    assert (c["batcher_frames_batched"] + c["batcher_dropped_malformed"]
            + c["batcher_dropped_overflow"] + c["batcher_dropped_closed"]
            == c["batcher_frames_offered"])


def test_batcher_buffer_pool_recycles_staging_arrays():
    """A recycled staging array is reused by a later batch (zero per-batch
    allocations in steady state) with its padding lanes re-zeroed; wrong
    shapes are silently refused."""
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    m = Metrics()
    b = FrameBatcher(batch_size=4, frame_shape=(4, 4), flush_timeout=0.01,
                     metrics=m)
    for i in range(4):
        b.put(np.full((4, 4), 7.0, np.float32), meta=i)
    first = b.get_batch()
    b.recycle(first.frames)
    b.recycle(np.zeros((2, 4, 4), np.float32))  # wrong shape: ignored
    b.put(np.full((4, 4), 1.0, np.float32), meta="x")
    second = b.get_batch()  # partial: deadline flush
    assert second.frames is first.frames  # the pooled buffer came back
    assert second.count == 1
    np.testing.assert_allclose(second.frames[1:], 0.0)  # padding re-zeroed
    assert m.counter("batcher_buffer_reuse") == 1


# ---------- overlapped serving pipeline (fake instant backend) ----------


def _instant_service(batch_size=8, frame_hw=(16, 16), **kwargs):
    from opencv_facerecognizer_tpu.runtime.fakes import InstantPipeline

    pipeline = InstantPipeline(frame_hw)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=batch_size, frame_shape=frame_hw,
        flush_timeout=0.05, similarity_threshold=0.0, **kwargs,
    )
    return pipeline, service, connector


def test_service_bucketed_dispatch_slices_partial_batches():
    """A partial batch dispatches at the smallest bucket >= its real frame
    count — never the full padded batch_size — and the slice is a view of
    the pooled staging array (no per-batch copy)."""
    pipeline, service, connector = _instant_service(
        batch_size=32, bucket_sizes=(8, 32))
    service.start(warmup=False)
    try:
        for i in range(3):
            connector.inject(FRAME_TOPIC,
                             {"frame": np.zeros((16, 16), np.float32),
                              "meta": {"i": i}})
        deadline = time.monotonic() + 10
        while (len(connector.messages(RESULT_TOPIC)) < 3
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        assert service.drain(timeout=10.0)
        service.stop()
    assert len(connector.messages(RESULT_TOPIC)) == 3
    assert pipeline.batch_sizes_seen == [8]  # 3 frames -> bucket 8, once
    assert service.metrics.counter("batches_bucketed") == 1


def test_service_continuous_batching_stats_and_zero_drops():
    """Full-rate traffic forms size-triggered batches; the trailing partial
    flushes at the adaptive deadline; nothing drops and every offered frame
    reconciles on the metrics surface."""
    _, service, connector = _instant_service(
        batch_size=4, target_latency_s=0.05)
    service.start(warmup=False)
    n = 10  # 2 full batches + a partial of 2
    try:
        for i in range(n):
            connector.inject(FRAME_TOPIC,
                             {"frame": np.zeros((16, 16), np.float32),
                              "meta": {"i": i}})
        deadline = time.monotonic() + 10
        while (len(connector.messages(RESULT_TOPIC)) < n
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        assert service.drain(timeout=10.0)
        service.stop()
    assert len(connector.messages(RESULT_TOPIC)) == n
    c = service.metrics.counters()
    assert c["batcher_frames_offered"] == n
    assert c["batcher_frames_batched"] == n
    assert c.get("batcher_dropped_overflow", 0) == 0
    assert c["batcher_batches_size"] >= 2
    assert c["batcher_batches_deadline"] >= 1
    assert c["frames_processed"] == n


# ---------- connectors ----------


def test_frame_codec_roundtrip():
    frame = RNG.uniform(0, 255, (12, 10)).astype(np.float32)
    decoded = decode_frame(encode_frame(frame))
    np.testing.assert_array_equal(decoded, frame)
    assert decoded.dtype == frame.dtype


def test_fake_connector_pubsub_and_record():
    c = FakeConnector()
    got = []
    c.subscribe("t1", lambda topic, m: got.append(m))
    c.publish("t1", {"a": 1})
    c.publish("t2", {"b": 2})
    assert got == [{"a": 1}]
    assert c.messages("t2") == [{"b": 2}]


def test_jsonl_connector_roundtrip_and_malformed():
    frames_in = io.StringIO(
        json.dumps({"topic": "x", "data": {"v": 1}}) + "\n"
        + "this is not json\n"
        + json.dumps({"no_topic": True}) + "\n"
        + json.dumps({"topic": "x", "data": {"v": 2}}) + "\n"
    )
    out = io.StringIO()
    c = JSONLConnector(frames_in, out)
    got = []
    c.subscribe("x", lambda t, m: got.append(m["v"]))
    c.start()
    for _ in range(100):
        if len(got) == 2:
            break
        time.sleep(0.01)
    c.stop()
    assert got == [1, 2]
    assert c.malformed_lines == 2
    c.publish("y", {"ok": True})
    assert json.loads(out.getvalue().strip()) == {"topic": "y", "data": {"ok": True}}


def test_ros_connector_clear_error_without_rospy():
    from opencv_facerecognizer_tpu.runtime.connector import ROSConnector

    with pytest.raises(ImportError, match="JSONLConnector"):
        ROSConnector()


# ---------- recognizer service over fake transport ----------


@pytest.fixture(scope="module")
def serving_stack():
    """Tiny trained detector+embedder+gallery on the 8-device CPU mesh."""
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder, normalize_faces, train_embedder,
    )
    from opencv_facerecognizer_tpu.ops import image as image_ops
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline

    FACE = (32, 32)
    scenes, boxes, counts = make_synthetic_scenes(48, (96, 96), max_faces=2, seed=31)
    det = CNNFaceDetector(features=(8, 16, 32), head_features=32, max_faces=4,
                          score_threshold=0.25)
    det.train(scenes, boxes, counts, steps=250, batch_size=16, learning_rate=2e-3)
    net = FaceEmbedNet(embed_dim=32, stem_features=8, stage_features=(8, 16),
                       stage_blocks=(1, 1))
    crops, labels = [], []
    for i in range(len(scenes)):
        for b in range(counts[i]):
            y0, x0, y1, x1 = boxes[i, b].astype(int)
            crops.append(np.asarray(image_ops.resize(scenes[i][y0:y1, x0:x1], FACE)))
            labels.append(i % 5)
    crops, labels = np.stack(crops), np.asarray(labels, np.int32)
    params = init_embedder(net, 5, FACE, seed=0)
    params = train_embedder(net, params, np.asarray(normalize_faces(crops, FACE)),
                            labels, steps=40, batch_size=16)
    mesh = make_mesh(tp=8)
    gallery = ShardedGallery(capacity=512, dim=32, mesh=mesh)
    emb = np.asarray(net.apply({"params": params["net"]}, normalize_faces(crops, FACE)))
    gallery.add(emb, labels)
    pipe = RecognitionPipeline(det, net, params["net"], gallery, face_size=FACE)
    return pipe, mesh


def _make_service(pipe, batch_size=4):
    connector = FakeConnector()
    service = RecognizerService(
        pipe, connector, batch_size=batch_size, frame_shape=(96, 96),
        flush_timeout=0.02, similarity_threshold=0.2,
        subject_names=[f"person_{i}" for i in range(5)],
    )
    return service, connector


def test_service_end_to_end_results(serving_stack):
    pipe, _ = serving_stack
    service, connector = _make_service(pipe)
    service.start()
    try:
        scenes, boxes, counts = make_synthetic_scenes(8, (96, 96), max_faces=2, seed=91)
        for i, scene in enumerate(scenes):
            connector.inject(FRAME_TOPIC, {**encode_frame(scene), "meta": {"frame_id": i}})
        deadline = time.monotonic() + 20
        while len(connector.messages(RESULT_TOPIC)) < 8 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        service.stop()
    results = connector.messages(RESULT_TOPIC)
    assert len(results) == 8
    frame_ids = sorted(r["meta"]["frame_id"] for r in results)
    assert frame_ids == list(range(8))
    found = sum(len(r["faces"]) for r in results)
    assert found >= int(counts.sum()) // 2
    for r in results:
        for f in r["faces"]:
            assert set(f) == {"box", "detection_score", "label", "name", "similarity"}
            assert f["name"].startswith(("person_", "unknown"))


def test_service_skips_malformed_frames(serving_stack):
    pipe, _ = serving_stack
    service, connector = _make_service(pipe, batch_size=2)
    service.start()
    try:
        connector.inject(FRAME_TOPIC, {"garbage": True})
        connector.inject(FRAME_TOPIC, {**encode_frame(np.zeros((10, 10), np.float32))})
        scene = make_synthetic_scenes(1, (96, 96), seed=5)[0][0]
        connector.inject(FRAME_TOPIC, {**encode_frame(scene), "meta": {"frame_id": 0}})
        deadline = time.monotonic() + 10
        while not connector.messages(RESULT_TOPIC) and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        service.stop()
    assert len(connector.messages(RESULT_TOPIC)) == 1
    assert service.metrics.counter("frames_malformed") == 1
    assert service.metrics.counter("frames_dropped") == 1


def test_service_enrolment_protocol(serving_stack):
    pipe, mesh = serving_stack
    service, connector = _make_service(pipe, batch_size=2)
    size_before = pipe.gallery.size
    service.start()
    try:
        connector.inject(CONTROL_TOPIC, {"cmd": "enroll", "subject": "newcomer", "count": 2})
        scenes, _, counts = make_synthetic_scenes(12, (96, 96), max_faces=1, seed=13)
        scenes = scenes[counts > 0]
        for i, scene in enumerate(scenes):
            connector.inject(FRAME_TOPIC, {**encode_frame(scene), "meta": i})
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            status = [m for m in connector.messages(STATUS_TOPIC) if m.get("status") == "enrolled"]
            if status:
                break
            time.sleep(0.05)
    finally:
        service.stop()
    enrolled = [m for m in connector.messages(STATUS_TOPIC) if m.get("status") == "enrolled"]
    assert enrolled and enrolled[0]["subject"] == "newcomer"
    assert pipe.gallery.size == size_before + 2
    assert "newcomer" in service.subject_names


def test_service_reload_without_drop(serving_stack):
    pipe, mesh = serving_stack
    from opencv_facerecognizer_tpu.parallel import ShardedGallery

    service, connector = _make_service(pipe)
    staged = ShardedGallery(capacity=512, dim=32, mesh=mesh)
    staged.add(RNG.normal(size=(3, 32)).astype(np.float32), np.array([9, 9, 9], np.int32))
    service.reload_gallery(staged)
    assert pipe.gallery.size == 3
    status = connector.messages(STATUS_TOPIC)
    assert status and status[-1]["status"] == "reloaded"


def test_service_stats_command(serving_stack):
    pipe, _ = serving_stack
    service, connector = _make_service(pipe)
    connector.inject(CONTROL_TOPIC, {"cmd": "stats"})
    stats = [m for m in connector.messages(STATUS_TOPIC) if m.get("status") == "stats"]
    assert stats and "gallery_size" in stats[0]


# ---------- trainer ----------


def test_trainer_classic_flow_and_checkpoint(tmp_path):
    from opencv_facerecognizer_tpu.utils import serialization

    X, y, names = make_synthetic_faces(5, 6, (24, 24), seed=41)
    trainer = TheTrainer(model="fisherfaces", image_size=(24, 24), kfold=3)
    path = str(tmp_path / "model.ckpt")
    model = trainer.train(X, y, names, model_path=path)
    assert trainer.mean_accuracy > 0.8
    restored = serialization.load_model(path)
    pred, _ = restored.predict(X[:4])
    assert (np.asarray(pred) == y[:4]).mean() == 1.0
    assert restored.subject_names == names


def test_trainer_model_zoo():
    # 40x40 keeps LBPH's 8x8 grid cells at a usable 4-5 px (the reference
    # default is 70x70; tiny cells starve the histograms)
    X, y, names = make_synthetic_faces(4, 5, (40, 40), seed=43)
    for model_type in ("eigenfaces", "lbph"):
        trainer = TheTrainer(model=model_type, image_size=(40, 40), kfold=2)
        trainer.train(X, y, names)
        assert trainer.mean_accuracy > 0.7, model_type


def test_trainer_lbp_fisherfaces_checkpoint(tmp_path):
    """The r5 robustness config (raw r=3 LBP 6x6 -> Fisherfaces -> cosine
    NN) trains, validates, and roundtrips through the msgpack checkpoint —
    the composite (ChainOperator + SpatialHistogram(ExtendedLBP r=3) +
    Fisherfaces + cosine NearestNeighbor) must all re-serialize."""
    from opencv_facerecognizer_tpu.utils import serialization

    # 48x48 keeps the 6x6 grid cells at ~7 px (r=3 LBP crops 3 px/side)
    X, y, names = make_synthetic_faces(5, 6, (48, 48), seed=41)
    trainer = TheTrainer(model="lbp_fisherfaces", image_size=(48, 48),
                         kfold=3)
    path = str(tmp_path / "model.ckpt")
    trainer.train(X, y, names, model_path=path)
    assert trainer.mean_accuracy > 0.8
    restored = serialization.load_model(path)
    pred, _ = restored.predict(X[:4])
    assert (np.asarray(pred) == y[:4]).mean() == 1.0
    assert restored.subject_names == names


def test_trainer_cnn_gallery_handoff():
    from opencv_facerecognizer_tpu.parallel import make_mesh

    X, y, names = make_synthetic_faces(4, 6, (32, 32), seed=47, noise=8.0)
    trainer = TheTrainer(
        model="cnn", image_size=(32, 32), kfold=0, embed_dim=32, train_steps=40,
        cnn_kwargs=dict(stem_features=8, stage_features=(8, 16), stage_blocks=(1, 1),
                        batch_size=16, learning_rate=3e-3),
    )
    trainer.train(X, y, names, validate=False)
    gallery = trainer.build_gallery(X, y, make_mesh(tp=8))
    assert gallery.size == len(y)
    emb = np.array(trainer.model.feature.extract(X[:8]))
    labels, sims, _ = (np.asarray(v) for v in gallery.match(emb, k=1))
    assert (labels[:, 0] == y[:8]).mean() >= 0.9
    # store_dtype handoff: build_gallery defaults to f32 while the
    # ocvf-recognize serving default is bf16 — swap_from casts the staged
    # snapshot to the serving width at install (round-5 advisor), so the
    # documented retrain -> reload_gallery handoff works without the
    # trainer knowing serving's dtype.
    import jax.numpy as jnp

    serving = trainer.build_gallery(X, y, make_mesh(tp=8),
                                    store_dtype=jnp.bfloat16)
    assert serving.data.embeddings.dtype == jnp.bfloat16
    staged = trainer.build_gallery(X, y, make_mesh(tp=8),
                                   capacity=serving.capacity,
                                   store_dtype=jnp.bfloat16)
    serving.swap_from(staged)  # dtype + capacity match: plain ref swap
    assert serving.data.embeddings.dtype == jnp.bfloat16
    serving.swap_from(gallery)  # f32 default into bf16 serving: cast
    assert serving.data.embeddings.dtype == jnp.bfloat16
    assert serving.size == gallery.size
    labels2, _, _ = (np.asarray(v) for v in serving.match(emb, k=1))
    assert (labels2[:, 0] == y[:8]).mean() >= 0.9


def test_trainer_rejects_unknown_model_and_field():
    with pytest.raises(TypeError):
        TheTrainer(bogus_field=1)
    trainer = TheTrainer(model="nope")
    with pytest.raises(ValueError):
        trainer.train(*make_synthetic_faces(2, 2, (16, 16)))


def test_trainer_classifier_swap(tmp_path):
    """The reference let any classifier pair with any feature; the trainer
    exposes nn | svm | kernel_svm over every model family."""
    from opencv_facerecognizer_tpu.models import KernelSVM, SVM
    from opencv_facerecognizer_tpu.utils import serialization

    X, y, names = make_synthetic_faces(5, 6, (24, 24), seed=41)
    for clf_kind, clf_type in (("svm", SVM), ("kernel_svm", KernelSVM)):
        trainer = TheTrainer(model="eigenfaces", image_size=(24, 24),
                             kfold=0, classifier=clf_kind)
        path = str(tmp_path / f"{clf_kind}.ckpt")
        trainer.train(X, y, names, validate=False, model_path=path)
        assert isinstance(trainer.model.classifier, clf_type)
        restored = serialization.load_model(path)
        pred, _ = restored.predict(X[:6])
        assert (np.asarray(pred) == y[:6]).mean() >= 0.8, clf_kind
    with pytest.raises(ValueError):
        TheTrainer(classifier="nope").train(X, y, names, validate=False)


def test_select_model_picks_measured_winner(tmp_path):
    """select_model k-folds every candidate on the same data, fits ONLY
    the winner on the full set, and checkpoints it (the 'which model?'
    question answered by measurement — SURVEY §2.1 Validation extension)."""
    from opencv_facerecognizer_tpu.runtime.trainer import select_model
    from opencv_facerecognizer_tpu.utils import serialization

    X, y, names = make_synthetic_faces(5, 6, (48, 48), seed=41)
    path = str(tmp_path / "auto.ckpt")
    winner, scores = select_model(
        X, y, names, candidates=("eigenfaces", "lbp_fisherfaces"),
        model_path=path, image_size=(48, 48), kfold=3)
    assert set(scores) == {"eigenfaces", "lbp_fisherfaces"}
    best = max(scores, key=scores.get)
    assert winner.config.model == best
    assert winner.mean_accuracy == scores[best]
    restored = serialization.load_model(path)
    pred, _ = restored.predict(X[:4])
    assert (np.asarray(pred) == y[:4]).mean() >= 0.75

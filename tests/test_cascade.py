"""Cascade early-exit detection (ISSUE 13): stage-1 FaceGate model,
the serving gate's ``completed_empty`` ledger settlement (exact
accounting mixed with drops/dead-letters, settle-span mirror, journal
rows), the ``cascade: reject-all`` chaos fault, brownout threshold
tightening, recompile-watchdog coverage of both stages, and the
face-density traffic-mix generator."""

import json

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
from opencv_facerecognizer_tpu.runtime.fakes import (
    InstantPipeline,
    synthetic_frame_stream,
)
from opencv_facerecognizer_tpu.runtime.faults import BOUNDARIES, FaultInjector
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RESULT_TOPIC,
    RecognizerService,
)
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils import tracing
from opencv_facerecognizer_tpu.utils.metrics import Metrics

HW = (32, 32)


def _service(metrics=None, tracer=None, journal=None, faults=None,
             cascade_stub=True, cascade=True, batch_size=8,
             bucket_sizes=(2, 4, 8), max_pending=None, **pipe_kwargs):
    metrics = metrics or Metrics()
    pipeline = InstantPipeline(HW, cascade_stub=cascade_stub,
                               faces_per_frame=1, **pipe_kwargs)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=batch_size, frame_shape=HW,
        flush_timeout=0.02, inflight_depth=2, similarity_threshold=0.0,
        metrics=metrics, tracer=tracer, dead_letter_journal=journal,
        fault_injector=faults, bucket_sizes=bucket_sizes, cascade=cascade)
    if max_pending is not None:
        service.batcher.max_pending = max_pending
    pipeline.prewarm_batch_shapes(service._bucket_ladder, HW,
                                  service.batcher.dtype)
    service._warmed = True
    return pipeline, service, connector, metrics


def _faced(seed=0):
    frame = np.random.default_rng(seed).integers(
        20, 90, size=HW).astype(np.uint8).astype(np.float32)
    frame[8:20, 8:20] = 200.0
    return frame


def _facefree(seed=0):
    return np.random.default_rng(seed).integers(
        20, 90, size=HW).astype(np.uint8).astype(np.float32)


def _drain_stop(service):
    assert service.drain(timeout=20.0)
    service.stop()


# ---- traffic-mix generator -------------------------------------------------


def test_synthetic_frame_stream_density_and_determinism():
    a = synthetic_frame_stream(40, HW, face_density=0.3, seed=11)
    b = synthetic_frame_stream(40, HW, face_density=0.3, seed=11)
    assert len(a) == 40
    # EXACT density (a seeded permutation, not bernoulli): 12 of 40.
    assert sum(1 for _f, k in a if k) == 12
    # Interleaved, not a prefix.
    faced_idx = [i for i, (_f, k) in enumerate(a) if k]
    assert faced_idx != list(range(12))
    for (fa, ka), (fb, kb) in zip(a, b):
        assert ka == kb
        np.testing.assert_array_equal(fa, fb)
    # Face frames carry the bright blob the stub cascade keys on.
    for frame, k in a:
        assert (frame.max() >= 150) == bool(k)


def test_synthetic_frame_stream_jpeg_composes():
    pytest.importorskip("PIL")
    from opencv_facerecognizer_tpu.runtime.ingest import decode_jpeg

    rows = synthetic_frame_stream(6, HW, face_density=0.5, seed=2,
                                  jpeg=True)
    assert len(rows) == 6
    for payload, frame, _k in rows:
        decoded = decode_jpeg(payload)
        assert decoded.shape == frame.shape


# ---- serving gate: settlement, compaction, spans, journal ------------------


def test_cascade_rejects_settle_completed_empty_with_results():
    _pipe, service, connector, metrics = _service()
    results = []
    connector.subscribe(RESULT_TOPIC, lambda t, m: results.append(m))
    service.start(warmup=False)
    for i in range(8):
        frame = _faced(i) if i % 2 == 0 else _facefree(i)
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": i}})
    _drain_stop(service)
    ledger = service.ledger()
    assert ledger["completed"] == 4
    assert ledger["completed_empty"] == 4
    assert ledger["in_system"] == 0
    # Every admitted frame got a result publish; rejected ones are empty
    # and stamped with the exit stage.
    assert len(results) == 8
    by_seq = {m["meta"]["seq"]: m for m in results}
    for i in range(8):
        if i % 2 == 0:
            assert by_seq[i].get("exit") != "cascade"
        else:
            assert by_seq[i]["faces"] == []
            assert by_seq[i]["exit"] == "cascade"


def test_cascade_compaction_dispatches_smaller_bucket():
    """Survivor compaction: a full batch with 2 face frames must reach
    stage 2 as the SMALLEST ladder bucket that fits the survivors, with
    metas still aligned to the right frames."""
    pipe, service, connector, _metrics = _service()
    results = []
    connector.subscribe(RESULT_TOPIC, lambda t, m: results.append(m))
    service.start(warmup=False)
    for i in range(8):
        frame = _faced(i) if i in (1, 6) else _facefree(i)
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": i}})
    _drain_stop(service)
    # 2 survivors out of 8 -> the b2 rung (ladder 2/4/8).
    assert 2 in pipe.batch_sizes_seen
    assert 8 not in pipe.batch_sizes_seen
    faced_seqs = {m["meta"]["seq"] for m in results if m.get("faces")}
    assert faced_seqs == {1, 6}


def test_cascade_full_batch_exit_skips_stage2():
    pipe, service, connector, metrics = _service()
    service.start(warmup=False)
    for i in range(16):
        connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    assert pipe.dispatches == 0  # stage 2 never ran
    assert pipe.cascade_calls > 0
    c = metrics.counters()
    assert c[mn.FRAMES_COMPLETED_EMPTY] == 16
    assert c[mn.CASCADE_BATCH_EXITS] > 0
    assert c[mn.CASCADE_FRAMES_SCORED] == 16
    # /prom rate gauges reflect the all-rejected stream.
    assert metrics.gauge(mn.CASCADE_REJECT_RATE) == 1.0
    assert metrics.gauge(mn.CASCADE_PASS_RATE) == 0.0


def test_cascade_disabled_by_flag_and_without_gate():
    # --no-cascade: the stub is present but the gate never runs.
    pipe, service, connector, metrics = _service(cascade=False)
    service.start(warmup=False)
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    assert pipe.cascade_calls == 0
    assert pipe.dispatches > 0
    assert metrics.counter(mn.FRAMES_COMPLETED) == 8
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == 0
    # No gate on the pipeline: cascade=True is the unchanged behavior.
    pipe2, service2, connector2, metrics2 = _service(cascade_stub=False)
    assert not service2._cascade_active
    service2.start(warmup=False)
    connector2.inject(FRAME_TOPIC, {"frame": _facefree(1), "meta": {}})
    _drain_stop(service2)
    assert metrics2.counter(mn.FRAMES_COMPLETED) == 1


def test_cascade_exact_ledger_with_drops_dead_letters_and_spans(tmp_path):
    """The accounting satellite: cascade rejections mixed with a stuck
    readback (dead-letter) and malformed frames must reconcile exactly —
    ledger, settle-span mirror (account_spans incl. completed_empty),
    and journal rows for every drop."""
    from opencv_facerecognizer_tpu.runtime.journal import DeadLetterJournal

    metrics = Metrics()
    tracer = tracing.Tracer(ring_size=1 << 12, sample=1.0)
    journal = DeadLetterJournal(str(tmp_path / "dead.jsonl"),
                                metrics=metrics)
    faults = FaultInjector(seed=3)
    faults.script("readback", "stuck")
    _pipe, service, connector, _ = _service(
        metrics=metrics, tracer=tracer, journal=journal, faults=faults)
    service.resilience.readback_deadline_s = 0.3
    service.start(warmup=False)
    # A full batch of faced frames first: it dispatches and its readback
    # sticks -> dead-letter.
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _faced(i),
                                       "meta": {"seq": i}})
    # Then a mixed wave (cascade rejects the face-free half) plus two
    # malformed frames (wrong shape).
    for i in range(8, 24):
        frame = _faced(i) if i % 2 else _facefree(i)
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": i}})
    for i in (90, 91):
        connector.inject(FRAME_TOPIC, {"frame": np.zeros((3, 3)),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    ledger = service.ledger()
    assert ledger["in_system"] == 0, ledger
    assert ledger["completed_empty"] == 8
    drops = ledger["drops_by_reason"]
    assert drops[mn.FRAMES_DEAD_LETTERED] == 8
    assert drops[mn.BATCHER_DROPPED_MALFORMED] == 2
    # Settle-span mirror: with sample=1.0 the spans reproduce the ledger
    # exactly, completed_empty included.
    spans = tracer.snapshot(FRAME_TOPIC)
    acct = tracing.account_spans(spans)
    assert acct["completed"] == int(ledger["completed"])
    assert acct["completed_empty"] == 8
    assert acct["drops"] == {k: int(v) for k, v in drops.items()}
    assert acct["traced"] == int(ledger["admitted"])
    # Journal rows cover the dead-lettered frames (cascade rejections are
    # completions, not drops — they must NOT be journaled).
    journal.close()
    rows = [json.loads(line)
            for line in (tmp_path / "dead.jsonl").read_text().splitlines()]
    assert sum(len(r["frames"]) for r in rows
               if r["reason"] == "dead_letter") == 8
    assert not any("cascade" in r["reason"] for r in rows)


def test_cascade_reject_all_chaos_degrades_cleanly():
    """A pathological stage 1 (the ``cascade: reject-all`` fault) must
    degrade to zero matches — every frame settles completed_empty, no
    wedge, no leaked frames, stage 2 never dispatches."""
    assert BOUNDARIES["cascade"] == ("reject_all",)
    faults = FaultInjector(seed=5, rates={"cascade": {"reject_all": 1.0}})
    pipe, service, connector, metrics = _service(faults=faults)
    service.start(warmup=False)
    for i in range(32):
        frame = _faced(i) if i % 2 else _facefree(i)
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": i}})
    _drain_stop(service)
    ledger = service.ledger()
    assert ledger["in_system"] == 0
    assert ledger["completed"] == 0
    assert ledger["completed_empty"] == 32
    assert pipe.dispatches == 0
    assert metrics.counter(mn.FACES_FOUND) == 0
    assert not service.loop_crashed
    assert faults.injected["cascade:reject_all"] > 0


def test_cascade_error_fails_open_to_full_detector():
    pipe, service, connector, metrics = _service()

    def broken(frames):
        raise RuntimeError("stage-1 backend blew up")

    pipe.cascade_scores = broken
    service.start(warmup=False)
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    # Fail OPEN: the full detector served every frame.
    assert metrics.counter(mn.FRAMES_COMPLETED) == 8
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == 0
    assert metrics.counter(mn.CASCADE_ERRORS) > 0
    assert service.ledger()["in_system"] == 0


def test_cascade_brownout_tightens_threshold():
    from opencv_facerecognizer_tpu.runtime.resilience import BrownoutPolicy

    pipeline = InstantPipeline(HW, cascade_stub=True)
    service = RecognizerService(
        pipeline, FakeConnector(), batch_size=8, frame_shape=HW,
        similarity_threshold=0.0, metrics=Metrics(),
        brownout=BrownoutPolicy(queue_wait_s=0.05),
        cascade_threshold=0.4, cascade_brownout_notch=0.2)
    assert service._effective_cascade_threshold() == 0.4
    service._brownout_level = 1
    assert service._effective_cascade_threshold() == pytest.approx(0.6)
    service._brownout_level = 0
    assert service._effective_cascade_threshold() == 0.4
    # Notch disabled -> no tightening.
    service.cascade_brownout_notch = 0.0
    service._brownout_level = 2
    assert service._effective_cascade_threshold() == 0.4


def test_cascade_recompile_watchdog_covers_stage1():
    pipe, service, connector, metrics = _service()
    service.start(warmup=False)
    # Forget the stage-1 compiles only: the next scored batch must read
    # as a post-warmup recompile even though stage 2 stays warm.
    pipe.compiled_cascade_sigs.clear()
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    assert metrics.counter(mn.RECOMPILES_POST_WARMUP) >= 1


def test_cascade_in_system_counts_empty_completions():
    _pipe, service, connector, _m = _service()
    service.start(warmup=False)
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                       "meta": {"seq": i}})
    _drain_stop(service)
    assert service.frames_in_system() == 0.0


# ---- registry / plumbing ---------------------------------------------------


def test_cascade_metric_names_registered():
    names = set(mn.all_names())
    for name in (mn.FRAMES_COMPLETED_EMPTY, mn.CASCADE_FRAMES_SCORED,
                 mn.CASCADE_BATCH_EXITS, mn.CASCADE_ERRORS,
                 mn.CASCADE_SCORE, mn.CASCADE_REJECT_RATE,
                 mn.CASCADE_PASS_RATE, mn.CASCADE_THRESHOLD):
        assert name in names
    from tools.ocvf_lint.wiring import ATTR_HINTS, HOT_PATH_SUFFIXES

    assert ATTR_HINTS["cascade"] == "FaceGate"
    assert any(s.endswith("models/cascade.py") for s in HOT_PATH_SUFFIXES)


def test_bench_compare_tracks_cascade_uplift():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "bench_compare.py"))
    bench_compare = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_compare)
    assert "cascade_uplift_density0" in bench_compare.METRICS
    doc = {"cascade": {"uplift": {"d0": {"uplift": 3.1}}}}
    extract = bench_compare.METRICS["cascade_uplift_density0"][0]
    assert extract(doc) == 3.1
    # Regression direction: candidate losing the uplift fails.
    report = bench_compare.compare(doc, {"cascade": {"uplift": {
        "d0": {"uplift": 1.0}}}})
    assert any(r["metric"] == "cascade_uplift_density0"
               and r["verdict"] == "regression" for r in report["metrics"])


def test_cascade_smoke_section_shape():
    """Fast variant of the bench_serving cascade section (the full gated
    run is ``bench_serving.py --smoke``; this keeps tier-1 quick and
    unflaky — structure and ledger exactness, not the timing gates)."""
    import bench_serving

    out = bench_serving.run_cascade_smoke(
        densities=(0.0, 0.3), seconds=0.4, watchdog_seconds=0.25,
        recall=False)
    assert set(out["uplift"]) == {"d0", "d30"}
    for row in out["uplift"].values():
        assert row["cascade_on"]["ledger_in_system_after_drain"] == 0
        assert row["cascade_off"]["ledger_in_system_after_drain"] == 0
        assert row["cascade_off"]["completed_empty"] == 0
    assert out["watchdog_ok"], out["watchdog"]
    assert out["reject_all"]["reject_all_ok"], out["reject_all"]
    assert out["recall"]["skipped"]
    assert "cascade_ok" in out


# ---- stage-1 model ---------------------------------------------------------


def test_tile_targets_mark_face_tiles():
    from opencv_facerecognizer_tpu.models.cascade import tile_targets

    boxes = np.array([[[16, 16, 48, 48], [0, 0, 0, 0]]], np.float32)
    t = tile_targets(boxes, np.array([1]), (96, 96), tile_px=16)
    assert t.shape == (1, 6, 6)
    # Center tile (2, 2) and its 1-tile dilation are positive.
    assert t[0, 2, 2] == 1.0
    assert t[0, 1, 1] == 1.0 and t[0, 3, 3] == 1.0
    assert t[0, 5, 5] == 0.0
    assert t.sum() == 9.0


def test_gate_loss_prefers_correct_tiles():
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.models.cascade import gate_loss

    targets = np.zeros((1, 4, 4), np.float32)
    targets[0, 1, 1] = 1.0
    good = np.full((1, 4, 4), -5.0, np.float32)
    good[0, 1, 1] = 5.0
    assert float(gate_loss(jnp.asarray(good), jnp.asarray(targets))) < float(
        gate_loss(jnp.asarray(-good), jnp.asarray(targets)))


@pytest.fixture(scope="module")
def trained_gate():
    from opencv_facerecognizer_tpu.models.cascade import FaceGate
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    scenes, boxes, counts = make_synthetic_scenes(96, (96, 96), max_faces=2,
                                                  seed=3)
    return FaceGate().train(scenes, boxes, counts, steps=300, batch_size=32)


def test_face_gate_separates_scenes(trained_gate):
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    held, _b, counts = make_synthetic_scenes(48, (96, 96), max_faces=2,
                                             seed=99)
    scores = np.asarray(trained_gate.score_batch(held))
    has = counts > 0
    # Recall-first operating point: EVERY face scene survives the default
    # threshold; most face-free scenes fall below it. "Most" is what the
    # chip bears out: the trained gate lets 10-40 % of empty scenes through
    # (PERF.md section 4, the skipped share of `replay` by seed; ROADMAP
    # Design 1), and 17 of these 24 (70.8 %) are turned away here.
    assert (scores[has] >= trained_gate.threshold).all()
    assert (scores[~has] < trained_gate.threshold).mean() >= 0.6


def test_evaluate_gate_detector_fp_is_not_recall_loss(trained_gate):
    """A detector false positive on a background frame is not a face the
    cascade can lose: with gt_counts it moves out of the recall
    denominator and into detector_fp_suppressed (a precision win)."""
    from opencv_facerecognizer_tpu.models.cascade import evaluate_gate
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    class FiresEverywhere:
        def detect_batch(self, chunk):
            n = len(chunk)
            return (np.zeros((n, 1, 4)), np.ones((n, 1)),
                    np.ones((n, 1), bool))

    held, _b, counts = make_synthetic_scenes(32, (96, 96), max_faces=2,
                                             seed=99)
    no_gt = evaluate_gate(trained_gate, FiresEverywhere(), held)
    with_gt = evaluate_gate(trained_gate, FiresEverywhere(), held,
                            gt_counts=counts)
    assert with_gt["stage1_recall"] == 1.0
    assert with_gt["detector_fp_frames"] == int((counts == 0).sum())
    assert with_gt["detector_fp_suppressed"] >= 1
    # The label-free form counts every stage-2 firing as detectable, so
    # the same gate scores lower — the conservative direction.
    assert no_gt["stage1_recall"] < with_gt["stage1_recall"]
    assert "detector_fp_frames" not in no_gt


def test_face_gate_save_load_roundtrip(tmp_path, trained_gate):
    from opencv_facerecognizer_tpu.models.cascade import FaceGate
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    path = str(tmp_path / "gate.msgpack")
    trained_gate.save(path)
    loaded = FaceGate.load(path)
    assert loaded.threshold == trained_gate.threshold
    held, _b, _c = make_synthetic_scenes(8, (96, 96), max_faces=2, seed=5)
    np.testing.assert_allclose(np.asarray(trained_gate.score_batch(held)),
                               np.asarray(loaded.score_batch(held)),
                               atol=1e-6)


def test_real_pipeline_cascade_scores_prewarm_and_serve():
    """The REAL RecognitionPipeline path: cascade_scores compiles
    cache-keyed per rung, warmup() covers both stages, and a service
    over it serves with zero post-warmup recompiles — an untrained gate
    (negative bias init) rejects everything, exercising the full-batch
    early exit + buffer recycle on the real staging path."""
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.models.cascade import FaceGate
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import (
        RecognitionPipeline,
    )

    det = CNNFaceDetector(features=(8, 16), head_features=8, max_faces=2,
                          space_to_depth=4)
    det.load_params(det.net.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, *HW)))["params"])
    net = FaceEmbedNet(embed_dim=8, stem_features=4, stage_features=(4,),
                       stage_blocks=(1,))
    emb_params = init_embedder(net, num_classes=2, input_shape=(8, 8),
                               seed=0)["net"]
    gallery = ShardedGallery(capacity=16, dim=8, mesh=make_mesh(tp=8))
    gallery.add(np.random.default_rng(0).normal(size=(4, 8)).astype(
        np.float32), np.arange(4, dtype=np.int32))
    gate = FaceGate(features=(4, 8))
    gate.load_params(gate.net.init(jax.random.PRNGKey(1),
                                   jnp.zeros((1, *HW)))["params"])
    pipeline = RecognitionPipeline(det, net, emb_params, gallery,
                                   face_size=(8, 8), cascade=gate)
    metrics = Metrics()
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=4, frame_shape=HW,
        flush_timeout=0.02, similarity_threshold=0.0, metrics=metrics,
        bucket_sizes=(2, 4))
    service.start(warmup=True)  # compiles ladder + BOTH cascade stages
    try:
        assert len(pipeline._cascade_cache) == 2  # one per rung
        for i in range(8):
            connector.inject(FRAME_TOPIC, {"frame": _facefree(i),
                                           "meta": {"seq": i}})
        assert service.drain(timeout=30.0)
    finally:
        service.stop()
    ledger = service.ledger()
    assert ledger["in_system"] == 0
    # Untrained gate (bias -2.0): every frame scores face-unlikely and
    # early-exits; no stage-2 dispatch, no post-warmup recompiles.
    assert ledger["completed_empty"] == 8
    assert metrics.counter(mn.RECOMPILES_POST_WARMUP) == 0

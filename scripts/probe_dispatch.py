"""Decompose the serving dispatch quote (VERDICT r3 item #6): where do the
pre-readback milliseconds of one ``recognize_batch_packed`` call go?

Measured terms (NO blocking readback happens anywhere in this process, so
every number is host-side dispatch cost):

- ``full_np_f32``: the serving quote — numpy f32 frames in, packed step
  dispatched (H2D + pjit arg handling + dispatch).
- ``h2d_only``: ``jnp.asarray`` of the same batch alone.
- ``full_device``: same call with frames ALREADY device-resident — the
  pjit python/arg-handling cost without the transfer.
- ``bare_pjit``: the cached compiled function called directly with
  precomputed snapshot/args — subtracts the pipeline wrapper's
  key-lookup/snapshot overhead.
- ``full_np_u8``: uint8 frames in (4x fewer H2D bytes, in-graph cast).

Writes the table into BENCH_SERVING.json under "dispatch_decomposition".

Run:  PYTHONPATH=. python scripts/probe_dispatch.py [--batch 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def p50_ms(ts):
    return round(float(np.percentile(ts, 50) * 1e3), 3)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n", type=int, default=30)
    ap.add_argument("--compile-wait-s", type=float, default=30.0,
                    help="async-compile settle time (no readback allowed)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        SERVING_EMBEDDER_KWARGS, SERVING_FACE_SIZE, FaceEmbedNet,
        init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    dev = jax.devices()[0]
    _log(f"device: {dev}")
    batch, h, w, max_faces = args.batch, 256, 256, 8
    dim = SERVING_EMBEDDER_KWARGS["embed_dim"]
    det = CNNFaceDetector(max_faces=max_faces, score_threshold=0.3)
    scenes, boxes, counts = make_synthetic_scenes(
        num_scenes=16, scene_size=(h, w), max_faces=max_faces,
        face_size_range=(24, 56), seed=7)
    det.train(scenes, boxes, counts, steps=20, batch_size=8)
    net = FaceEmbedNet(**SERVING_EMBEDDER_KWARGS)
    emb_params = init_embedder(net, num_classes=16,
                               input_shape=SERVING_FACE_SIZE, seed=0)["net"]
    rng = np.random.default_rng(0)
    # bf16 rows: the ocvf-recognize serving default (gallery_dtype A/B)
    gallery = ShardedGallery(capacity=16384, dim=dim, mesh=make_mesh(),
                             store_dtype=jnp.bfloat16)
    gallery.add(rng.normal(size=(16384, dim)).astype(np.float32),  # ocvf-lint: boundary=wal-before-mutate -- probe fixture: synthetic gallery for dispatch timing, no state dir
                rng.integers(0, 512, 16384).astype(np.int32))
    pipe = RecognitionPipeline(det, net, emb_params, gallery,
                               face_size=SERVING_FACE_SIZE)

    frames_np = [np.asarray(scenes[i % len(scenes)]).astype(np.float32)
                 for i in range(batch)]
    batch_np = np.stack(frames_np)
    pipe.recognize_batch_packed(batch_np)  # compile (async)
    time.sleep(args.compile_wait_s)

    N = args.n
    rows = {}

    ts = []
    for i in range(N):
        b = np.stack(frames_np)
        t0 = time.perf_counter(); pipe.recognize_batch_packed(b)
        ts.append(time.perf_counter() - t0)
    rows["full_np_f32_ms"] = p50_ms(ts)

    ts = []
    for i in range(N):
        b = np.stack(frames_np)
        t0 = time.perf_counter(); jnp.asarray(b)
        ts.append(time.perf_counter() - t0)
    rows["h2d_only_ms"] = p50_ms(ts)

    dev_frames = jnp.asarray(batch_np)
    ts = []
    for i in range(N):
        t0 = time.perf_counter(); pipe.recognize_batch_packed(dev_frames)
        ts.append(time.perf_counter() - t0)
    rows["full_device_ms"] = p50_ms(ts)

    data = gallery.data
    key = pipe._step_key(dev_frames, data)
    fn = pipe._packed_cache[key]
    ts = []
    for i in range(N):
        t0 = time.perf_counter()
        fn(det.params, emb_params, data.embeddings, data.valid, data.labels,
           dev_frames)
        ts.append(time.perf_counter() - t0)
    rows["bare_pjit_ms"] = p50_ms(ts)

    # Params/gallery CLOSED OVER as jit constants: per-call argument
    # processing shrinks to the frames leaf alone. bare_pjit - bound_pjit
    # isolates the pytree-flatten share of the dispatch quote (the
    # serving step passes ~hundreds of param leaves per call on a 1-core
    # host) — the measured basis for a pre-bound serving fast path
    # (VERDICT r4 #4: pre-bound compiled calls / snapshot reuse).
    det_p, emb_p = det.params, emb_params
    g_emb, g_val, g_lab = data.embeddings, data.valid, data.labels

    @jax.jit
    def bound(fr):
        return fn(det_p, emb_p, g_emb, g_val, g_lab, fr)

    bound(dev_frames)  # compile (async) — a FULL retrace of the serving
    # graph with constants folded, so give it the full settle window
    time.sleep(args.compile_wait_s)
    ts = []
    for i in range(N):
        t0 = time.perf_counter()
        bound(dev_frames)
        ts.append(time.perf_counter() - t0)
    rows["bound_pjit_ms"] = p50_ms(ts)

    frames_u8 = [f.astype(np.uint8) for f in frames_np]
    pipe.recognize_batch_packed(np.stack(frames_u8))  # compile u8 variant
    time.sleep(args.compile_wait_s / 2)
    ts = []
    for i in range(N):
        b = np.stack(frames_u8)
        t0 = time.perf_counter(); pipe.recognize_batch_packed(b)
        ts.append(time.perf_counter() - t0)
    rows["full_np_u8_ms"] = p50_ms(ts)

    result = {
        "batch": batch,
        "frame_hw": [h, w],
        "device": str(dev),
        "date": time.strftime("%Y-%m-%d"),
        "note": ("p50 over pre-sync-poll dispatch-only calls (no readback "
                 "in-process). wrapper overhead = full_device - bare_pjit; "
                 "H2D share = full_np_f32 - full_device (compare h2d_only); "
                 "pjit arg handling + dispatch = bare_pjit; pytree-flatten "
                 "share = bare_pjit - bound_pjit (params closed over as "
                 "constants)."),
        **rows,
    }
    path = os.path.join(REPO, "BENCH_SERVING.json")
    try:
        doc = json.load(open(path))
    except (OSError, json.JSONDecodeError):
        doc = {}
    doc.setdefault("dispatch_decomposition", {})[str(batch)] = result
    from opencv_facerecognizer_tpu.utils.serialization import atomic_write_json

    atomic_write_json(path, doc)
    _log("merged dispatch_decomposition into BENCH_SERVING.json")
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()

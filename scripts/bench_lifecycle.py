"""Gallery lifecycle at scale, mid-serving, on the real chip (VERDICT
round-2 item #5): serve at 16k enrolled rows -> enroll past
``PALLAS_MIN_CAPACITY`` (auto-grow doubles capacity AND switches the
matcher from the XLA materialize form to the pallas streaming kernel) ->
keep growing to 1M rows -> measure the steady in-pipeline cost at each
stage and the one-off stall each growth causes.

What the artifact records (merged into BENCH_DETAIL.json under
"lifecycle"; bench.py preserves the section):

- ``steady_ms_per_batch`` at 16k / 128k / 1M rows, timed by the same
  chained-differencing instrument bench.py uses;
- ``grow_stall_ms`` per growth event: wall time of the FIRST
  ``recognize_batch_packed`` call after ``gallery.add`` crossed capacity —
  the XLA recompile + (at 64k->128k) the matcher switch the serving thread
  actually eats; subsequent-call time recorded alongside to show recovery;
- ``install_ms``: install cost of the grown snapshot (since PR 38 the
  served rows are copied into the doubled tier on the devices and only the
  added rows cross the link: ``ShardedGallery._grown_arrays`` /
  ``_splice_rows``).

Run:  PYTHONPATH=. python scripts/bench_lifecycle.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def chained_ms_per_batch(pipeline, frames_stack):
    """Shared chained-differencing instrument (utils.benchtime) over the
    fused recognize step, folding every output into the chain scalar."""
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.utils.benchtime import scalar_chain_ms

    data = pipeline.gallery.data
    key = pipeline._step_key(frames_stack[0], data)
    if key not in pipeline._step_cache:
        pipeline._step_cache[key] = pipeline._build_step(
            *frames_stack[0].shape, capacity=data.capacity)
    step = pipeline._step_cache[key]

    def scalar(det_p, emb_p, g_emb, g_valid, g_lab, frames):
        res = step(det_p, emb_p, g_emb, g_valid, g_lab, frames)
        return (jnp.sum(res.similarities) + jnp.sum(res.boxes) * 1e-6
                + jnp.sum(res.valid))

    return scalar_chain_ms(scalar, (
        pipeline.detector.params, pipeline.embed_params, data.embeddings,
        data.valid, data.labels, frames_stack[0],
    ))


def main():
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    dev = jax.devices()[0]
    _log(f"device: {dev}")
    from opencv_facerecognizer_tpu.models.embedder import (
        SERVING_EMBEDDER_KWARGS, SERVING_FACE_SIZE,
    )

    batch, h, w, max_faces = 32, 256, 256, 8
    dim = SERVING_EMBEDDER_KWARGS["embed_dim"]

    det = CNNFaceDetector(max_faces=max_faces, score_threshold=0.3)
    scenes, boxes, counts = make_synthetic_scenes(
        num_scenes=48, scene_size=(h, w), max_faces=max_faces,
        face_size_range=(24, 56), seed=7)
    det.train(scenes, boxes, counts, steps=150, batch_size=16)
    net = FaceEmbedNet(**SERVING_EMBEDDER_KWARGS)
    emb_params = init_embedder(net, num_classes=16,
                               input_shape=SERVING_FACE_SIZE,
                               seed=0)["net"]

    rng = np.random.default_rng(0)
    mesh = make_mesh()
    # async_grow: the serving configuration — overflow stages rows, a
    # background worker compiles the next tier (pipeline prewarm hook) and
    # installs it off the serving path (VERDICT r3 item #5).
    # bf16 rows = the ocvf-recognize serving default (half the grow-upload
    # bytes and HBM; measured 1.24x faster 1M-row match — gallery_dtype
    # section); this artifact must measure the configuration that ships.
    gallery = ShardedGallery(capacity=16384, dim=dim, mesh=mesh,
                             async_grow=True, store_dtype=jnp.bfloat16)
    gallery.add(rng.standard_normal((16384, dim), dtype=np.float32),  # ocvf-lint: boundary=wal-before-mutate -- bench fixture: synthetic throwaway gallery, no state dir, nothing durable at stake
                rng.integers(0, 512, 16384).astype(np.int32))
    pipeline = RecognitionPipeline(det, net, emb_params, gallery,
                                   face_size=SERVING_FACE_SIZE)

    frames_stack = jnp.stack([
        jnp.asarray(make_synthetic_scenes(
            num_scenes=batch, scene_size=(h, w), max_faces=max_faces,
            face_size_range=(24, 56), seed=100 + i)[0], jnp.float32)
        for i in range(4)
    ])
    one_batch = np.asarray(frames_stack[0])

    result = {"batch": batch, "stages": [], "grow_events": []}

    def steady(tag):
        ms = chained_ms_per_batch(pipeline, frames_stack)
        if ms is None:  # chain delta never cleared readback quantization
            result["stages"].append({
                "rows": gallery.size, "capacity": gallery.capacity,
                "pallas": gallery._pallas_enabled(),
                "steady_ms_per_batch": None, "invalid": "under-resolved",
            })
            _log(f"[{tag}] UNRESOLVED steady timing")
            return
        result["stages"].append({
            "rows": gallery.size, "capacity": gallery.capacity,
            "pallas": gallery._pallas_enabled(),
            "steady_ms_per_batch": round(ms, 3),
        })
        _log(f"[{tag}] rows={gallery.size} cap={gallery.capacity} "
             f"pallas={gallery._pallas_enabled()} steady {ms:.3f} ms/batch")

    # serve at 16k (XLA matcher), establish steady state
    _ = np.asarray(pipeline.recognize_batch_packed(one_batch))  # warm
    steady("16k")

    def grow_to(total_rows, tag):
        """Enroll up to total_rows mid-serving. With async_grow the add
        stages the rows and returns; serving continues on the OLD tier
        (every call timed) while the worker compiles + installs the new
        one; the first call at the NEW tier is the residual stall."""
        need = total_rows - gallery.size
        # Generate OUTSIDE the timed window: 920k f64 gaussians measured
        # 107 s on this 1-core host — timing it inside the add() window
        # reported the bench's own data generation as a 113 s "stall"
        # (r5 first lifecycle capture). f32 generation is also ~4x faster.
        rows = rng.standard_normal((need, dim), dtype=np.float32)
        labs = rng.integers(0, 512, need).astype(np.int32)
        t_add0 = time.perf_counter()
        gallery.add(rows, labs)  # ocvf-lint: boundary=wal-before-mutate -- bench fixture: the measured grow path itself, synthetic rows, no durability contract
        add_return_ms = (time.perf_counter() - t_add0) * 1e3
        # serve continuously until the grow lands; record every call
        during = []
        while not gallery.wait_ready(timeout=0):
            t0 = time.perf_counter()
            _ = np.asarray(pipeline.recognize_batch_packed(one_batch))
            during.append((time.perf_counter() - t0) * 1e3)
        visibility_s = time.perf_counter() - t_add0
        t0 = time.perf_counter()
        _ = np.asarray(pipeline.recognize_batch_packed(one_batch))
        first_ms = (time.perf_counter() - t0) * 1e3  # first NEW-tier call
        t0 = time.perf_counter()
        _ = np.asarray(pipeline.recognize_batch_packed(one_batch))
        second_ms = (time.perf_counter() - t0) * 1e3
        result["grow_events"].append({
            "to_rows": gallery.size, "to_capacity": gallery.capacity,
            "pallas_after": gallery._pallas_enabled(),
            "add_return_ms": round(add_return_ms, 1),
            "serving_calls_during_grow": len(during),
            "during_grow_ms_max": round(max(during), 1) if during else None,
            "during_grow_ms_p50": round(float(np.median(during)), 1)
                                  if during else None,
            "enroll_visibility_s": round(visibility_s, 2),
            "grow_stall_ms": round(first_ms, 1),
            "next_call_ms": round(second_ms, 1),
            "worker_decomposition_s": dict(gallery.last_grow_info),
        })
        _log(f"[{tag}] grew to {gallery.size} rows (cap {gallery.capacity}, "
             f"pallas={gallery._pallas_enabled()}): add returned in "
             f"{add_return_ms:.0f} ms, {len(during)} serving calls during "
             f"grow (max {max(during) if during else 0:.0f} ms), visible "
             f"after {visibility_s:.1f} s, first new-tier call "
             f"{first_ms:.0f} ms, next {second_ms:.0f} ms; worker "
             f"{gallery.last_grow_info}")

    # cross PALLAS_MIN_CAPACITY: 16k -> 80k rows => capacity doubles past
    # 64k and the matcher switches to the streaming kernel
    grow_to(80_000, "grow->128k")
    steady("128k")
    # then to 1M rows (capacity 1,048,576)
    grow_to(1_000_000, "grow->1M")
    steady("1M")

    detail_path = os.path.join(REPO, "BENCH_DETAIL.json")
    try:
        detail = json.load(open(detail_path))
    except (OSError, json.JSONDecodeError):
        detail = {}
    detail["lifecycle"] = {
        "device": str(dev),
        "date": time.strftime("%Y-%m-%d"),
        "note": ("serve@16k -> enroll past PALLAS_MIN_CAPACITY (matcher "
                 "switch) -> 1M rows, all mid-serving on one pipeline "
                 "object with async_grow: the overflowing add returns in "
                 "milliseconds, serving continues on the old tier while "
                 "the grow worker compiles the new tier (pipeline prewarm "
                 "hook) and installs it; grow_stall_ms is the first "
                 "recognize call at the NEW tier (wall-clock incl. its "
                 "readback), enroll_visibility_s "
                 "is the staged-rows-to-matchable latency, and "
                 "worker_decomposition_s breaks the background work into "
                 "prewarm (compile) / copy / normalize (staged rows) / "
                 "upload_wait (H2D + residency poll, off the serving "
                 "path) / install (the atomic publish)"),
        **result,
    }
    from opencv_facerecognizer_tpu.utils.serialization import atomic_write_json

    atomic_write_json(detail_path, detail)
    _log("merged lifecycle section into BENCH_DETAIL.json")
    print(json.dumps(detail["lifecycle"], indent=2))


if __name__ == "__main__":
    main()

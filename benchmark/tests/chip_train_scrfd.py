"""Trains the detector and the gate of an SCRFD configuration on the chip,
once, so that they can be committed under ``benchmark/nets/<hash>/`` and no
run of the cell trains them in set-up:

    chiprun -- python3 benchmark/tests/chip_train_scrfd.py <config name>

The files go to ``chiprun_out/nets/<recipe hash>/``. Besides what
``recognize_scrfd.train_nets`` says of them, this prints what the choice of
``pre_nms`` rests on (anchors over the threshold a frame, faces found by
``pre_nms``) and the detector's time alone at the top rung, host clock round
``block_until_ready``. The benchmark's own runs never run this.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmark import run

    with open(os.path.join(ROOT, "benchmark", "configs", argv[0] + ".json")) as fh:
        config = json.load(fh)
    device = run.device_gate(int(config["devices"]))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import render
    from benchmark.stacks import recognize_scrfd
    from opencv_facerecognizer_tpu.models import scrfd
    from opencv_facerecognizer_tpu.models.detector import evaluate_detector
    from opencv_facerecognizer_tpu.utils import compile_cache

    compile_cache.enable()
    tag = recognize_scrfd.recipe_hash(config)
    out_dir = os.path.join(ROOT, "chiprun_out", "nets", tag)
    seen = recognize_scrfd.train_nets(config, out_dir, run.say)

    det = scrfd.load_detector(os.path.join(out_dir, "detector.ckpt"))
    recipe = config["nets"]["gate_and_detector"]
    frame_size = tuple(config["frame_size"])
    rng = np.random.default_rng([int(recipe["seed"]), 99])
    faces = int(config["max_faces"])
    scenes = np.zeros((32, *frame_size), np.float32)
    boxes = np.zeros((32, faces, 4), np.float32)
    counts = np.full((32,), faces, np.int32)
    for i in range(32):  # as the cell's traffic: every frame holds max_faces faces
        who = [int(v) for v in rng.integers(0, 1 << 20, size=faces)]
        scenes[i], boxes[i] = render.render_scene(frame_size, who,
                                                  tuple(recipe["face_px"]), rng)
    logits = []
    forward = jax.jit(lambda p, x: scrfd.flatten_outputs(det.net.apply({"params": p}, x))[0])
    for i in range(0, 32, 8):
        logits.append(np.asarray(forward(det.params, jnp.asarray(scenes[i:i + 8]))))
    over = (1 / (1 + np.exp(-np.concatenate(logits))) > det.score_threshold).sum(axis=1)
    seen["anchors_over_threshold_per_full_frame"] = {
        "mean": float(over.mean()), "max": int(over.max()), "min": int(over.min())}
    by_pre_nms = {}
    for pre_nms in (32, 64, 128, 256, 512):
        other = scrfd.SCRFDDetector(**{**det.config(), "pre_nms": pre_nms})
        other.load_params(det.params)
        got = evaluate_detector(other, scenes, boxes, counts, batch_size=8)
        by_pre_nms[pre_nms] = {k: round(float(got[k]), 4) for k in
                               ("recall", "precision", "mean_matched_iou")}
    seen["full_frames_by_pre_nms"] = by_pre_nms

    top = int(config["recognize_args"]["--batch-size"])
    frames = jnp.asarray(np.resize(scenes, (top, *frame_size)))
    jax.block_until_ready(det._detect_jit(det.params, frames))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(det._detect_jit(det.params, frames))
        times.append(time.perf_counter() - t0)
    seen["detect_alone_ms_at_top_rung_host_clock"] = [round(t * 1e3, 2) for t in times]
    seen["recipe_hash"] = tag
    print(json.dumps({"seen": seen, "device": device}), flush=True)
    with open(os.path.join(ROOT, "chiprun_out", f"train_{tag}.json"), "w") as fh:
        json.dump(seen, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

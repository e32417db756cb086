"""The system under test for configurations whose ``"stack"`` is
``"recognize_scrfd"``: the serving stack of ``stacks/recognize.py``, built
by that module's own ``build``, with an SCRFD detector and a stage-1 gate
trained at the configuration's own frame size, and the IResNet embedder of
``stacks/recognize_iresnet.py``.

Detector and gate are found under a recipe hash of their own, which covers
the configuration's ``detector`` entry, its training recipe
(``nets.gate_and_detector``) and the sources training runs through
(``models/scrfd.py``, ``models/cascade.py``, ``benchmark/render.py``): the
committed files under ``benchmark/nets/<hash>/`` or, where the sources have
moved on, files trained here into ``.bench_work/nets/<hash>/`` (minutes on
the chip: the first run of such a tree pays them in ``setup_s``). The
embedder's checkpoint is made from the seed by
``recognize_iresnet.make_embedder``. With the three files gathered where
``recognize.find_nets`` looks for the configuration, ``recognize.build``
goes on as for any configuration: ``_load_stack`` tells the detector's
class by the checkpoint's header.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from benchmark import render
from benchmark.stacks import recognize, recognize_iresnet
from benchmark.stacks.recognize import reference_rows  # noqa: F401  (run.py asks the stack's module for it)

#: program and benchmark sources whose change can change what training gives
RECIPE_SOURCES = (
    "opencv_facerecognizer_tpu.models.scrfd",
    "opencv_facerecognizer_tpu.models.cascade",
    "benchmark.render",
)
NET_FILES = ("detector.ckpt", "cascade.ckpt")
#: the keys of the configuration's ``detector`` entry that ``SCRFDDetector`` takes
DETECTOR_KWARGS = ("stem_features", "stage_features", "stage_blocks",
                   "neck_features", "head_features", "head_convs", "head_groups",
                   "num_anchors", "strides_share", "score_threshold",
                   "iou_threshold", "pre_nms")


def detector_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    spec = config["detector"]
    return {**{k: spec[k] for k in DETECTOR_KWARGS},
            "max_faces": int(config["max_faces"])}


def recipe_hash(config: Dict[str, Any]) -> str:
    """Names detector and gate by what made them."""
    digest = hashlib.sha256()
    digest.update(json.dumps({"recipe": config["nets"]["gate_and_detector"],
                              "detector": detector_kwargs(config),
                              "frame_size": config["frame_size"]},
                             sort_keys=True).encode())
    for module in RECIPE_SOURCES:
        with open(importlib.util.find_spec(module).origin, "rb") as fh:
            digest.update(fh.read())
    return digest.hexdigest()[:12]


def find_nets(config: Dict[str, Any]) -> Tuple[Optional[str], str]:
    """(directory that holds the matching detector and gate or None, the hash)."""
    tag = recipe_hash(config)
    for base in (os.path.join(recognize.BENCH_DIR, "nets"),
                 os.path.join(recognize.work_dir(), "nets")):
        path = os.path.join(base, tag)
        if all(os.path.isfile(os.path.join(path, f)) for f in NET_FILES):
            return path, tag
    return None, tag


def render_scenes(recipe: Dict[str, Any], frame_size: Tuple[int, int],
                  count: int, rng: np.random.Generator):
    """(frames [n, H, W] float32, padded yxyx boxes, counts): every fourth
    scene empty (the gate has to learn them), the others 1 to
    ``scene_max_faces`` faces of ``face_px``."""
    max_boxes = int(recipe["scene_max_faces"])
    scenes = np.zeros((count, *frame_size), np.float32)
    boxes = np.zeros((count, max_boxes, 4), np.float32)
    counts = np.zeros((count,), np.int32)
    for i in range(count):
        n = 0 if i % 4 == 0 else int(rng.integers(1, max_boxes + 1))
        who = [int(v) for v in rng.integers(0, 1 << 20, size=n)]
        scenes[i], boxes[i, :n] = render.render_scene(
            frame_size, who, tuple(recipe["face_px"]), rng)
        counts[i] = n
    return scenes, boxes, counts


def train_nets(config: Dict[str, Any], out_dir: str,
               say: Callable[[str], None]) -> Dict[str, float]:
    """SCRFD detector and stage-1 gate, trained with the program's own
    training code on scenes this benchmark renders at the configuration's
    frame size; returns what it saw of them on scenes held out."""
    from opencv_facerecognizer_tpu.models.cascade import FaceGate
    from opencv_facerecognizer_tpu.models.detector import evaluate_detector
    from opencv_facerecognizer_tpu.models.scrfd import SCRFDDetector

    recipe = config["nets"]["gate_and_detector"]
    frame_size = tuple(config["frame_size"])
    rng = np.random.default_rng(int(recipe["seed"]))
    os.makedirs(out_dir, exist_ok=True)
    scenes, boxes, counts = render_scenes(recipe, frame_size,
                                          int(recipe["scenes"]), rng)
    held, held_boxes, held_counts = render_scenes(recipe, frame_size, 32, rng)

    t0 = time.perf_counter()
    det = SCRFDDetector(**detector_kwargs(config))
    det.train(scenes, boxes, counts, steps=int(recipe["detector_steps"]),
              batch_size=int(recipe["detector_batch"]),
              learning_rate=float(recipe["detector_learning_rate"]),
              calibration_frames=int(recipe["detector_calibration_frames"]),
              seed=int(recipe["seed"]))
    det.save(os.path.join(out_dir, "detector.ckpt"))
    seen = evaluate_detector(det, held, held_boxes, held_counts, batch_size=8)
    say(f"nets: SCRFD detector {recipe['detector_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s; on 32 scenes held out: recall "
        f"{seen['recall']:.3f}, precision {seen['precision']:.3f}, IoU of the "
        f"matched {seen['mean_matched_iou']:.3f} ({seen['num_pred']} found of "
        f"{seen['num_gt']})")

    t0 = time.perf_counter()
    gate = FaceGate(**recipe["gate_kwargs"]).train(
        scenes, boxes, counts, steps=int(recipe["gate_steps"]),
        seed=int(recipe["seed"]))
    gate.save(os.path.join(out_dir, "cascade.ckpt"))
    scores = np.asarray(gate.score_batch(held))
    seen["gate_lowest_face"] = float(scores[held_counts > 0].min())
    seen["gate_highest_empty"] = float(scores[held_counts == 0].max())
    say(f"nets: gate {recipe['gate_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s; on the scenes held out the lowest "
        f"score of a face scene is {seen['gate_lowest_face']:.3f}, the highest "
        f"of an empty one {seen['gate_highest_empty']:.3f}")
    return seen


def ensure_nets(config: Dict[str, Any], say: Callable[[str], None]) -> Dict[str, Any]:
    path, tag = find_nets(config)
    trained = path is None
    if trained:
        path = os.path.join(recognize.work_dir(), "nets", tag)
        say(f"nets: no detector and gate for recipe {tag} under benchmark/nets "
            f"or .bench_work/nets; training into {path}")
        train_nets(config, path, say)
    else:
        say(f"nets: detector and gate of recipe {tag} found at "
            f"{os.path.relpath(path, recognize.ROOT)}")
    return {"dir": path, "hash": tag, "trained_now": trained}


def prepare_nets(config: Dict[str, Any], say: Callable[[str], None]
                 ) -> Tuple[float, Dict[str, Any]]:
    """Puts the three checkpoints where ``recognize.find_nets`` looks for
    the configuration's recipe; returns the seconds the embedder took and
    where detector and gate came from."""
    out_dir = os.path.join(recognize.work_dir(), "nets",
                           recognize.recipe_hash(config))
    os.makedirs(out_dir, exist_ok=True)
    theirs = ensure_nets(config, say)
    for name in NET_FILES:
        shutil.copyfile(os.path.join(theirs["dir"], name),
                        os.path.join(out_dir, name))
    t0 = time.perf_counter()
    seen = recognize_iresnet.make_embedder(
        config, os.path.join(out_dir, "embedder.ckpt"))
    seconds = time.perf_counter() - t0
    say(f"nets: IResNet embedder drawn from seed {config['nets']['seed']} and "
        f"calibrated in {seconds:.1f} s: {seen['multiply_adds'] / 1e9:.3f} G "
        f"multiply-adds, {seen['parameters'] / 1e6:.2f} M parameters")
    return seconds, theirs


def build(config: Dict[str, Any], traffic, seed: int,
          say: Callable[[str], None], trace: bool = False) -> recognize.Stack:
    """``recognize.build`` over the nets gathered and made here; the
    embedder's seconds are ``embedder_make`` in the set-up split."""
    if importlib.util.find_spec(RECIPE_SOURCES[0]) is None:
        # the parent of the PR that brought the detector: fail at once
        raise SystemExit(f"benchmark: this program has no {RECIPE_SOURCES[0]}; "
                         f"it cannot run configuration {config['name']!r}")
    t0 = time.perf_counter()
    embedder_s, theirs = prepare_nets(config, say)
    gather_s = time.perf_counter() - t0 - embedder_s
    stack = recognize.build(config, traffic, seed, say, trace=trace)
    kind = getattr(stack.pipeline.detector, "kind", None)
    if kind != "scrfd":
        raise RuntimeError(f"the stack serves a detector of kind {kind!r}, "
                           f"the configuration states SCRFD")
    stack.nets["detector_and_gate"] = theirs
    stack.split["embedder_make"] = embedder_s
    stack.split["nets"] += gather_s
    return stack

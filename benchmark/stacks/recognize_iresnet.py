"""The system under test for configurations whose ``"stack"`` is
``"recognize_iresnet"``: the serving stack of ``stacks/recognize.py``,
built by that module's own ``build``, with an IResNet embedder whose
checkpoint is made here from the configuration's seed.

A learned IResNet-50 is 174 MB of float32 and cannot be committed or
fetched, so set-up makes one: parameters drawn from ``nets.seed``, the
BatchNorms' stored moments from one calibration pass over rendered faces,
written through the program's ``serialization`` by the feature class itself
into ``.bench_work/nets/<hash>/embedder.ckpt``. Gate and detector are the
committed files of the recipe ``nets.gate_and_detector`` names (trained
here by ``stacks/recognize.py`` if its sources have moved on). With the
three files in place ``recognize.build`` finds them under the
configuration's own recipe hash and goes on as for any configuration.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Callable, Dict

import numpy as np

from benchmark import render
from benchmark.stacks import recognize
from benchmark.stacks.recognize import reference_rows  # noqa: F401  (run.py asks the stack's module for it)


def calibration_faces(config: Dict[str, Any]) -> np.ndarray:
    """[n, h, w] rendered faces at the embedder's input size, drawn from
    ``nets.seed``: one identity a face, an equal share at each of
    ``embedder_calibration_px`` (a face rendered smaller is resized up, as
    a crop of a small face in a frame is)."""
    from opencv_facerecognizer_tpu.ops import image as image_ops

    recipe = config["nets"]
    size = tuple(config["face_size"])
    rng = np.random.default_rng([int(recipe["seed"]), 31])
    sides = [int(px) for px in recipe["embedder_calibration_px"]]
    share = int(recipe["embedder_calibration_faces"]) // len(sides)
    faces = []
    for px in sides:
        at_px = np.concatenate([
            render.render_enrolment(int(rng.integers(0, 1 << 20)), (px, px), 1, rng)
            for _ in range(share)])
        faces.append(np.asarray(image_ops.resize(at_px, size)))
    return np.concatenate(faces)


def make_embedder(config: Dict[str, Any], path: str) -> Dict[str, float]:
    """Writes the configuration's embedder checkpoint; returns what it
    measured of the net while it had it."""
    from opencv_facerecognizer_tpu.models.classifier import NearestNeighbor
    from opencv_facerecognizer_tpu.models.iresnet import (
        IResNetEmbedding, multiply_adds, parameter_count)
    from opencv_facerecognizer_tpu.models.model import PredictableModel
    from opencv_facerecognizer_tpu.ops.distance import CosineDistance
    from opencv_facerecognizer_tpu.utils import serialization

    spec = config["embedder"]
    feature = IResNetEmbedding(
        embed_dim=spec["embed_dim"], input_size=spec["input_size"],
        stem_features=spec["stem_features"],
        stage_features=spec["stage_features"],
        stage_blocks=spec["stage_blocks"], in_channels=spec["in_channels"],
        eps=spec["eps"], seed=int(config["nets"]["seed"]))
    emb = np.asarray(feature.compute(calibration_faces(config)))
    sims = emb @ emb.T
    off = sims[~np.eye(len(sims), dtype=bool)]
    serialization.save_model(path, PredictableModel(
        feature, NearestNeighbor(CosineDistance())))
    return {"multiply_adds": float(multiply_adds(feature.net, feature.input_size)),
            "parameters": float(parameter_count(feature._params["net"])),
            "calibration_sim_mean": float(off.mean()),
            "calibration_sim_max": float(off.max())}


def prepare_nets(config: Dict[str, Any], say: Callable[[str], None]) -> float:
    """Puts the three checkpoints where ``recognize.find_nets`` looks for
    the configuration's recipe; returns the seconds the embedder took."""
    tag = recognize.recipe_hash(config)
    out_dir = os.path.join(recognize.work_dir(), "nets", tag)
    os.makedirs(out_dir, exist_ok=True)
    theirs = recognize.ensure_nets(
        {**config, "nets": config["nets"]["gate_and_detector"]}, say)
    for name in ("detector.ckpt", "cascade.ckpt"):
        shutil.copyfile(os.path.join(theirs["dir"], name),
                        os.path.join(out_dir, name))
    t0 = time.perf_counter()
    seen = make_embedder(config, os.path.join(out_dir, "embedder.ckpt"))
    seconds = time.perf_counter() - t0
    say(f"nets: IResNet embedder drawn from seed {config['nets']['seed']} and "
        f"calibrated in {seconds:.1f} s: {seen['multiply_adds'] / 1e9:.3f} G "
        f"multiply-adds, {seen['parameters'] / 1e6:.2f} M parameters; over the "
        f"calibration faces the similarity of two faces is "
        f"{seen['calibration_sim_mean']:.3f} in the mean, "
        f"{seen['calibration_sim_max']:.3f} at most")
    return seconds


def build(config: Dict[str, Any], traffic, seed: int,
          say: Callable[[str], None], trace: bool = False) -> recognize.Stack:
    """``recognize.build`` over the nets made and gathered here; the
    embedder's seconds are ``embedder_make`` in the set-up split."""
    t0 = time.perf_counter()
    embedder_s = prepare_nets(config, say)
    gather_s = time.perf_counter() - t0 - embedder_s
    stack = recognize.build(config, traffic, seed, say, trace=trace)
    stack.split["embedder_make"] = embedder_s
    stack.split["nets"] += gather_s
    return stack

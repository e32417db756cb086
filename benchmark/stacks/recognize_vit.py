"""The system under test for configurations whose ``"stack"`` is
``"recognize_vit"``: the serving stack of ``stacks/recognize.py``, built by
that module's own ``build``, with a vision-transformer embedder whose
checkpoint is made here from the configuration's seed
(``stacks/recognize_iresnet.py``'s shape, for the other embedder family).

A learned ViT-B is 455 MB of float32 and cannot be committed or fetched, so
set-up makes one: parameters drawn from ``nets.seed``, the head's
BatchNorms' stored moments from one calibration pass over the rendered
faces ``recognize_iresnet.calibration_faces`` draws, written through the
program's ``serialization`` by the feature class itself into
``.bench_work/nets/<hash>/embedder.ckpt``. Gate and detector are the
committed files of the recipe ``nets.gate_and_detector`` names. With the
three files in place ``recognize.build`` finds them under the
configuration's own recipe hash and goes on as for any configuration.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import time
from typing import Any, Callable, Dict

import numpy as np

from benchmark.stacks import recognize
from benchmark.stacks.recognize import reference_rows  # noqa: F401  (run.py asks the stack's module for it)
from benchmark.stacks.recognize_iresnet import calibration_faces

#: the configuration's ``embedder`` entry -> the feature class's arguments
FEATURE_ARGS = ("embed_dim", "input_size", "depth", "heads", "patch", "mlp_ratio",
                "out_dim", "in_channels", "layer_norm_eps", "head_bn_eps")


def make_embedder(config: Dict[str, Any], path: str) -> Dict[str, float]:
    """Writes the configuration's embedder checkpoint; returns what it
    measured of the net while it had it."""
    from opencv_facerecognizer_tpu.models.classifier import NearestNeighbor
    from opencv_facerecognizer_tpu.models.model import PredictableModel
    from opencv_facerecognizer_tpu.models.vit import (
        ViTEmbedding, multiply_adds, parameter_count)
    from opencv_facerecognizer_tpu.ops.distance import CosineDistance
    from opencv_facerecognizer_tpu.utils import serialization

    spec = config["embedder"]
    feature = ViTEmbedding(**{key: spec[key] for key in FEATURE_ARGS},
                           init_std=float(config["nets"]["embedder_init_std"]),
                           seed=int(config["nets"]["seed"]))
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
    say(f"nets: ViT embedder drawn from seed {config['nets']['seed']} and "
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
    if importlib.util.find_spec("opencv_facerecognizer_tpu.models.vit") is None:
        # the parent of the PR that brought the net: fail at once
        raise SystemExit("benchmark: this program has no "
                         "opencv_facerecognizer_tpu.models.vit; it cannot run "
                         f"configuration {config['name']!r}")
    t0 = time.perf_counter()
    embedder_s = prepare_nets(config, say)
    gather_s = time.perf_counter() - t0 - embedder_s
    stack = recognize.build(config, traffic, seed, say, trace=trace)
    stack.split["embedder_make"] = embedder_s
    stack.split["nets"] += gather_s
    return stack

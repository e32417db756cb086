"""Fisherfaces robustness attack (VERDICT r4 next-step #8).

The hard Yale-B-analog row (30x12, illumination 0.7, noise 14, HARD_POSE)
measures 0.8283 with TanTriggs -> Fisherfaces -> NN, and the independent
oracle confirms 0.8306 is the LINEAR subspace's ceiling on this
distribution — so this script attacks the *algorithm*, not the
implementation, with the robustness toolbox the framework already ships:

- locality: SpatialHistogram(LBP) features survive occluding rectangles
  (a cutout corrupts a few cells, not every projection coefficient the
  way it corrupts a global Fisher axis);
- discriminative locality: SpatialHistogram -> Fisherfaces (PCA->LDA on
  the histogram vector) keeps the local robustness while re-adding the
  supervised projection;
- occlusion-robust distances: chi-square / histogram-intersection / BRD
  family on histogram features;
- nonlinear decision: KernelSVM(rbf) over the Fisher projection.

Every candidate runs the EXACT BASELINE protocol (same generator, seed,
folds: scripts/measure_accuracy.py fisherfaces row) via the public
PredictableModel + KFoldCrossValidation surface. Results append to
scripts/.fisher_attack.jsonl; the winner (if it clears the 0.87 bar)
graduates to a measured row in BASELINE.md.

Accuracy is backend-independent (same math on CPU and TPU; the classic
models' device graphs are identical modulo fp reassociation), so this
sweep runs wherever it is launched — use --cpu to force the host backend.

Run:  PYTHONPATH=. python scripts/explore_fisherfaces.py [--cpu]
      [--only NAME ...]
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

OUT = os.path.join(REPO, "scripts", ".fisher_attack.jsonl")

#: the BASELINE fisherfaces_yaleb protocol, verbatim (measure_accuracy.py)
PROTOCOL = dict(num_subjects=30, per_subject=12, size=(70, 70), seed=2,
                illumination=0.7, noise=14.0, rotation=8.0,
                scale_jitter=0.08, elastic=1.2, occlusion=0.25)
FOLDS = 10


def candidates():
    """name -> thunk building (feature, classifier). Thunks import lazily so
    --only doesn't pay for unused graphs."""
    from opencv_facerecognizer_tpu.models.classifier import (
        KernelSVM, NearestNeighbor,
    )
    from opencv_facerecognizer_tpu.models.feature import (
        Fisherfaces, SpatialHistogram, TanTriggsPreprocessing,
    )
    from opencv_facerecognizer_tpu.models.operators import (
        ChainOperator, CombineOperator,
    )
    from opencv_facerecognizer_tpu.ops import lbp as lbp_ops
    from opencv_facerecognizer_tpu.ops.distance import (
        ChiSquareDistance, CosineDistance, EuclideanDistance,
        HistogramIntersection, L1BinRatioDistance,
    )

    tt = lambda: TanTriggsPreprocessing(sigma0=2.0, sigma1=4.0)  # noqa: E731
    elbp = lambda r: lbp_ops.ExtendedLBP(radius=r, neighbors=8)  # noqa: E731

    def hist(r=2, sz=(8, 8)):
        return SpatialHistogram(elbp(r), sz=sz)

    return {
        # the measured baseline, re-run here so every comparison shares one
        # code path + session
        "baseline_fisher_nn": lambda: (
            ChainOperator(tt(), Fisherfaces()),
            NearestNeighbor(EuclideanDistance()),
        ),
        # nonlinear decision over the same linear feature
        "fisher_rbf_svm": lambda: (
            ChainOperator(tt(), Fisherfaces()),
            KernelSVM(kernel="rbf"),
        ),
        # locality only (the lbph recipe pointed at THIS protocol)
        "lbp_chi2": lambda: (
            ChainOperator(tt(), hist()),
            NearestNeighbor(ChiSquareDistance()),
        ),
        "lbp_histint": lambda: (
            ChainOperator(tt(), hist()),
            NearestNeighbor(HistogramIntersection()),
        ),
        "lbp_l1brd": lambda: (
            ChainOperator(tt(), hist()),
            NearestNeighbor(L1BinRatioDistance()),
        ),
        # discriminative locality: LDA over the local histograms
        "lbp_fisher_cosine": lambda: (
            ChainOperator(tt(), ChainOperator(hist(), Fisherfaces())),
            NearestNeighbor(CosineDistance()),
        ),
        "lbp_fisher_nn": lambda: (
            ChainOperator(tt(), ChainOperator(hist(), Fisherfaces())),
            NearestNeighbor(EuclideanDistance()),
        ),
        # finer grid: more cells -> finer occlusion containment
        "lbp10_fisher_cosine": lambda: (
            ChainOperator(tt(), ChainOperator(hist(sz=(10, 10)), Fisherfaces())),
            NearestNeighbor(CosineDistance()),
        ),
        "lbp10_chi2": lambda: (
            ChainOperator(tt(), hist(sz=(10, 10))),
            NearestNeighbor(ChiSquareDistance()),
        ),
        # round 2 (after every round-1 challenger measured BELOW the 0.8283
        # baseline): ensembles + preprocessing ablations
        # global Fisher axes and local LBP-Fisher axes see different error
        # modes (illumination gradient vs occlusion); concatenate them
        "combine_fisher_lbpfisher": lambda: (
            CombineOperator(
                ChainOperator(tt(), Fisherfaces()),
                ChainOperator(tt(), ChainOperator(hist(), Fisherfaces())),
            ),
            NearestNeighbor(CosineDistance()),
        ),
        # LBP is illumination-invariant by construction — TanTriggs's
        # gamma+DoG may be destroying the texture LBP codes
        "rawlbp_chi2": lambda: (
            hist(),
            NearestNeighbor(ChiSquareDistance()),
        ),
        "rawlbp_fisher_cosine": lambda: (
            ChainOperator(hist(), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        # k=3 neighbor voting over the strong baseline feature
        "fisher_knn3": lambda: (
            ChainOperator(tt(), Fisherfaces()),
            NearestNeighbor(EuclideanDistance(), k=3),
        ),
        "fisher_cosine": lambda: (
            ChainOperator(tt(), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        # round 3: refine the round-2 winner (rawlbp_fisher_cosine 0.93)
        "rawlbp1_fisher_cosine": lambda: (
            ChainOperator(hist(r=1), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        "rawlbp10_fisher_cosine": lambda: (
            ChainOperator(hist(sz=(10, 10)), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        "rawlbp6_fisher_cosine": lambda: (
            ChainOperator(hist(sz=(6, 6)), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        "rawlbp_fisher_euclid": lambda: (
            ChainOperator(hist(), Fisherfaces()),
            NearestNeighbor(EuclideanDistance()),
        ),
        "rawlbp_fisher_knn3": lambda: (
            ChainOperator(hist(), Fisherfaces()),
            NearestNeighbor(CosineDistance(), k=3),
        ),
        # round 4: grid/radius around the 6x6 winner (0.9617)
        "rawlbp4_fisher_cosine": lambda: (
            ChainOperator(hist(sz=(4, 4)), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        "rawlbp5_fisher_cosine": lambda: (
            ChainOperator(hist(sz=(5, 5)), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
        "rawlbp6r3_fisher_cosine": lambda: (
            ChainOperator(hist(r=3, sz=(6, 6)), Fisherfaces()),
            NearestNeighbor(CosineDistance()),
        ),
    }


def run_candidate(name, build):
    from opencv_facerecognizer_tpu.models.model import PredictableModel
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_faces
    from opencv_facerecognizer_tpu.utils.validation import KFoldCrossValidation

    X, y, _ = make_synthetic_faces(**PROTOCOL)
    feature, classifier = build()
    model = PredictableModel(feature, classifier)
    t0 = time.perf_counter()
    cv = KFoldCrossValidation(k=FOLDS).validate(model, X, y)
    return {
        "name": name,
        "accuracy": round(float(cv.mean_accuracy), 4),
        "folds": FOLDS,
        "protocol": "fisherfaces_yaleb HARD (BASELINE row)",
        "seconds": round(time.perf_counter() - t0, 1),
        "date": time.strftime("%Y-%m-%d"),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force the host backend (accuracy is backend-"
                         "independent)")
    ap.add_argument("--only", action="append")
    args = ap.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    print(f"backend: {jax.devices()[0].platform}", file=sys.stderr)

    cands = candidates()
    selected = args.only or list(cands)
    for name in selected:
        if name not in cands:
            raise SystemExit(f"unknown candidate {name!r}; have {sorted(cands)}")
        row = run_candidate(name, cands[name])
        row["backend"] = jax.devices()[0].platform
        with open(OUT, "a") as fh:
            fh.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

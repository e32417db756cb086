"""``ocvf-train``: dataset dir -> validated, checkpointed model.

The reference flow (SURVEY.md §3.1): walk folder-per-subject dataset,
resize, fit Fisherfaces+NN, k-fold validate, save. Flags cover the §5.6
config surface; ``--model cnn`` swaps in the ArcFace CNN backend.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ocvf-train", description="Train a face recognition model on TPU"
    )
    p.add_argument("dataset", help="dataset dir: one sub-folder of images per subject")
    p.add_argument("model_path", help="output checkpoint path (.ckpt)")
    p.add_argument("--model", default="fisherfaces",
                   choices=["fisherfaces", "eigenfaces", "lbph",
                            "lbp_fisherfaces", "cnn", "auto"],
                   help="model family; 'auto' k-folds every family on the "
                        "dataset and keeps the measured winner")
    p.add_argument("--image-size", type=int, nargs=2, default=(70, 70),
                   metavar=("H", "W"))
    p.add_argument("--kfold", type=int, default=3)
    p.add_argument("--num-components", type=int, default=0)
    p.add_argument("--knn-k", type=int, default=1)
    p.add_argument("--no-tan-triggs", action="store_true")
    p.add_argument("--classifier", default="nn",
                   choices=["nn", "svm", "kernel_svm"],
                   help="classifier stage over the feature projection")
    p.add_argument("--svm-kernel", default="rbf",
                   choices=["rbf", "poly", "linear"],
                   help="kernel for --classifier kernel_svm")
    p.add_argument("--embed-dim", type=int, default=128)
    p.add_argument("--train-steps", type=int, default=200)
    p.add_argument("--eigenfaces-plot", default=None,
                   help="optional PNG path: render top subspace components")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler trace of the whole train+"
                        "validate run into this directory (open with "
                        "TensorBoard or xprof)")
    p.add_argument("--keep-checkpoints", type=int, default=0,
                   help="retain the previous N model checkpoints as "
                        "model.ckpt.1..N when overwriting (the write "
                        "itself is always atomic: tmp + fsync + rename, "
                        "so a crash mid-save never corrupts the existing "
                        "checkpoint)")
    return p


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    # Contradictory combinations fail loudly instead of silently training a
    # different model than the flags suggest.
    if args.svm_kernel != "rbf" and args.classifier != "kernel_svm":
        parser.error("--svm-kernel only applies with --classifier kernel_svm")
    if args.knn_k != 1 and args.classifier != "nn":
        parser.error(f"--knn-k only applies with --classifier nn "
                     f"(got --classifier {args.classifier})")
    from opencv_facerecognizer_tpu.utils import compile_cache

    compile_cache.enable()
    from opencv_facerecognizer_tpu.runtime.trainer import TheTrainer, TrainerConfig

    if args.model == "auto":
        # Flags that select a specific artifact shape don't compose with
        # selection — fail loudly (this file's policy) instead of silently
        # ignoring them.
        if args.profile_dir or args.eigenfaces_plot or args.keep_checkpoints:
            parser.error("--profile-dir/--eigenfaces-plot/--keep-checkpoints "
                         "don't apply with --model auto (selection saves "
                         "candidate models repeatedly; run the winner "
                         "single-model to use them)")
        from opencv_facerecognizer_tpu.runtime.trainer import select_model
        from opencv_facerecognizer_tpu.utils import dataset as dataset_utils

        images, labels, names = dataset_utils.read_images(
            args.dataset, image_size=tuple(args.image_size))
        trainer, scores = select_model(
            images, labels, names, model_path=args.model_path,
            image_size=tuple(args.image_size), kfold=args.kfold,
            num_components=args.num_components, knn_k=args.knn_k,
            tan_triggs=not args.no_tan_triggs, embed_dim=args.embed_dim,
            train_steps=args.train_steps,
            classifier=args.classifier, svm_kernel=args.svm_kernel,
        )
        for kind in sorted(scores, key=scores.get, reverse=True):
            print(f"  {kind:>16}: {scores[kind]:.4f} k-fold")
        print(f"selected: {trainer.config.model} "
              f"({trainer.mean_accuracy:.4f} mean k-fold accuracy)")
        print(f"model saved to {args.model_path}")
        return 0

    config = TrainerConfig(
        model=args.model,
        image_size=tuple(args.image_size),
        kfold=args.kfold,
        num_components=args.num_components,
        knn_k=args.knn_k,
        tan_triggs=not args.no_tan_triggs,
        classifier=args.classifier,
        svm_kernel=args.svm_kernel,
        embed_dim=args.embed_dim,
        train_steps=args.train_steps,
    )
    trainer = TheTrainer(config)
    trainer.keep_checkpoints = args.keep_checkpoints
    if args.profile_dir:
        import jax

        jax.profiler.start_trace(args.profile_dir)
    try:
        model = trainer.train_from_dir(args.dataset, model_path=args.model_path)
    finally:
        if args.profile_dir:
            import jax

            jax.profiler.stop_trace()
            print(f"profile trace written to {args.profile_dir}", file=sys.stderr)
    if trainer.validation:
        for result in trainer.validation.results:
            print(result)
        print(f"mean k-fold accuracy: {trainer.mean_accuracy:.4f}")
    print(f"subjects: {model.subject_names}")
    print(f"model saved to {args.model_path}")
    if args.eigenfaces_plot:
        from opencv_facerecognizer_tpu.models import Fisherfaces, PCA
        from opencv_facerecognizer_tpu.models.operators import FeatureOperator
        from opencv_facerecognizer_tpu.utils import visual

        feature = model.feature
        while isinstance(feature, FeatureOperator):
            feature = feature.model2
        if isinstance(feature, (PCA, Fisherfaces)):
            path = visual.plot_eigenfaces(feature, tuple(args.image_size),
                                          filename=args.eigenfaces_plot)
            print(f"eigenfaces plot: {path}")
        else:
            print("eigenfaces plot skipped: model has no subspace components")
    return 0


if __name__ == "__main__":
    sys.exit(main())

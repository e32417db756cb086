"""TheTrainer: end-to-end enrolment (SURVEY.md §2.1 "Trainer", §3.1).

The reference walked a dataset dir, resized to ~70x70, built
Fisherfaces + NearestNeighbor(Euclidean, k=1), k-fold validated, and
pickled the model. This rebuild keeps that flow and adds the CNN backend:

- ``model="fisherfaces" | "eigenfaces" | "lbph"`` — the classic plugins
  (BASELINE.json:7-9 configs), trained and validated exactly like the
  reference but batched on device.
- ``model="lbp_fisherfaces"`` — the round-5 robustness winner (raw LBP
  spatial histograms -> Fisherfaces -> cosine NN; measured rationale at
  the `_build_model` branch and in BASELINE.md).
- ``model="cnn"`` — ArcFace-trained CNN embedder; ``build_gallery()`` then
  yields the ShardedGallery + nets for the serving pipeline.

Checkpoints go through utils.serialization (msgpack, pickle-free).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from opencv_facerecognizer_tpu.models import (
    ChainOperator,
    ExtendedPredictableModel,
    Fisherfaces,
    KernelSVM,
    NearestNeighbor,
    PCA,
    SVM,
    SpatialHistogram,
    TanTriggsPreprocessing,
)
from opencv_facerecognizer_tpu.models.embedder import (
    SERVING_EMBEDDER_KWARGS, CNNEmbedding,
)
from opencv_facerecognizer_tpu.ops import lbp as lbp_ops
from opencv_facerecognizer_tpu.ops.distance import (
    ChiSquareDistance,
    CosineDistance,
    EuclideanDistance,
)
from opencv_facerecognizer_tpu.utils import dataset as dataset_utils
from opencv_facerecognizer_tpu.utils import serialization
from opencv_facerecognizer_tpu.utils.validation import KFoldCrossValidation


@dataclass
class TrainerConfig:
    """Flat config (SURVEY.md §5.6): one dataclass, no magic."""

    model: str = "fisherfaces"  # fisherfaces | eigenfaces | lbph | lbp_fisherfaces | cnn
    image_size: Tuple[int, int] = (70, 70)
    kfold: int = 3
    num_components: int = 0  # subspace dims (0 = auto)
    knn_k: int = 1
    tan_triggs: bool = True
    # classifier stage: nn (default, per model family) | svm | kernel_svm —
    # the reference's facerec lineage let any classifier pair with any
    # feature (SURVEY.md §2.1 "Classifiers": NearestNeighbor and SVM).
    classifier: str = "nn"
    svm_kernel: str = "rbf"  # kernel_svm only: rbf | poly | linear
    # cnn backend knobs
    embed_dim: int = 128
    train_steps: int = 200
    cnn_kwargs: Dict[str, Any] = field(default_factory=dict)


class TheTrainer:
    """Train + validate + checkpoint a recognition model from a dataset."""

    def __init__(self, config: Optional[TrainerConfig] = None, **overrides):
        self.config = config or TrainerConfig()
        for key, value in overrides.items():
            if not hasattr(self.config, key):
                raise TypeError(f"unknown TrainerConfig field {key!r}")
            setattr(self.config, key, value)
        self.model: Optional[ExtendedPredictableModel] = None
        self.validation: Optional[KFoldCrossValidation] = None
        #: previous model checkpoints retained on save (rotated to
        #: ``<model_path>.1..N``); 0 = overwrite only (still atomic).
        self.keep_checkpoints = 0

    # ---- model zoo ----

    def _build_model(self, subject_names: List[str]) -> ExtendedPredictableModel:
        cfg = self.config
        if cfg.model == "fisherfaces":
            feature = Fisherfaces(cfg.num_components)
            if cfg.tan_triggs:
                # sigma0=2, sigma1=4 (vs the paper's 1/2): the wider DoG
                # band removes more of the smooth illumination gradient —
                # 10-fold on the Yale-B analog: 0.8117 -> 0.9717
                # (BASELINE.md measured row).
                feature = ChainOperator(
                    TanTriggsPreprocessing(sigma0=2.0, sigma1=4.0), feature
                )
            classifier = NearestNeighbor(EuclideanDistance(), k=cfg.knn_k)
        elif cfg.model == "eigenfaces":
            feature = PCA(cfg.num_components)
            classifier = NearestNeighbor(EuclideanDistance(), k=cfg.knn_k)
        elif cfg.model == "lbph":
            # radius=2: measured k-fold accuracy on the noisy LFW-analog
            # jumps 0.76 -> 0.99 vs the radius=1 default (and stays equal
            # or better on clean data) — the wider ring's bilinear sampling
            # is effectively denoising the codes.
            feature = SpatialHistogram(
                lbp_ops.ExtendedLBP(radius=2, neighbors=8), sz=(8, 8)
            )
            classifier = NearestNeighbor(ChiSquareDistance(), k=cfg.knn_k)
        elif cfg.model == "lbp_fisherfaces":
            # The measured robustness winner on the hard Yale-B analog
            # (scripts/explore_fisherfaces.py, round 5): RAW ExtendedLBP
            # spatial histograms -> Fisherfaces -> cosine NN. Measured
            # surprises driving the design: (a) NO TanTriggs — LBP codes
            # are illumination-invariant already, and the DoG band-pass
            # destroys the micro-texture they encode (with TT: 0.8067;
            # raw: 0.93+); (b) a COARSE 6x6 grid beats 8x8/10x10 — fewer,
            # bigger cells give the LDA a denser histogram basis;
            # (c) radius 3 > 2 > 1. Hard-protocol k-fold: 0.9817 vs
            # 0.8283 for classic Fisherfaces (seed 2), and on UNSEEN
            # generator seeds {22, 42}: 0.9817/0.9950 vs 0.55/0.585 — the
            # classic's 0.83 was a lucky seed; this config's robustness
            # replicates (+0.15 over the pixel-space linear oracle
            # ceiling, BASELINE.md).
            feature = ChainOperator(
                SpatialHistogram(
                    lbp_ops.ExtendedLBP(radius=3, neighbors=8), sz=(6, 6)
                ),
                Fisherfaces(cfg.num_components),
            )
            classifier = NearestNeighbor(CosineDistance(), k=cfg.knn_k)
        elif cfg.model == "cnn":
            serialization.register(CNNEmbedding)
            # The serving embedder's structure (stem/stage widths, block,
            # norm) is the trainer's default, so ``ocvf-train --model cnn
            # --embed-dim 256 --image-size 64 64`` yields exactly the net
            # ``SERVING_EMBEDDER_KWARGS`` names; ``cnn_kwargs`` overrides.
            feature = CNNEmbedding(**{
                **SERVING_EMBEDDER_KWARGS,
                "embed_dim": cfg.embed_dim,
                "input_size": cfg.image_size,
                "train_steps": cfg.train_steps,
                **cfg.cnn_kwargs,
            })
            classifier = NearestNeighbor(CosineDistance(), k=cfg.knn_k)
        else:
            raise ValueError(f"unknown model type {self.config.model!r}")
        if cfg.classifier == "svm":
            classifier = SVM()
        elif cfg.classifier == "kernel_svm":
            classifier = KernelSVM(kernel=cfg.svm_kernel)
        elif cfg.classifier != "nn":
            raise ValueError(
                f"unknown classifier {cfg.classifier!r}; pick nn | svm | kernel_svm"
            )
        return ExtendedPredictableModel(
            feature, classifier, image_size=cfg.image_size, subject_names=subject_names
        )

    # ---- training flows ----

    def train_from_dir(self, dataset_path: str, model_path: Optional[str] = None):
        images, labels, names = dataset_utils.read_images(
            dataset_path, image_size=self.config.image_size
        )
        return self.train(images, labels, names, model_path)

    def train(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        subject_names: List[str],
        model_path: Optional[str] = None,
        validate: bool = True,
    ) -> ExtendedPredictableModel:
        from opencv_facerecognizer_tpu.ops import image as image_ops

        images = np.asarray(images, np.float32)
        if images.shape[1:] != tuple(self.config.image_size):
            images = np.asarray(image_ops.resize(images, self.config.image_size))
        labels = np.asarray(labels, np.int32)
        model = self._build_model(subject_names)
        if validate and self.config.kfold > 1:
            # Validation refits per fold on a scratch model so the final fit
            # below sees the full dataset.
            scratch = self._build_model(subject_names)
            self.validation = KFoldCrossValidation(k=self.config.kfold)
            self.validation.validate(scratch, images, labels)
        model.compute(images, labels)
        self.model = model
        if model_path:
            # Atomic write (tmp+fsync+rename) — a crash mid-save keeps the
            # previous checkpoint; keep_checkpoints>0 also rotates it to
            # model.ckpt.1..N so retrains retain history.
            serialization.save_model(model_path, model,
                                     keep_previous=self.keep_checkpoints)
        return model

    @property
    def mean_accuracy(self) -> float:
        return self.validation.mean_accuracy if self.validation else float("nan")

    # ---- model selection ----

    #: k-fold selection order for ``select_model``: cheap classics first,
    #: the CNN backend last (it trains longest). The round-5 measured
    #: default winner (lbp_fisherfaces) sits where its train cost does.
    SELECT_CANDIDATES = ("eigenfaces", "fisherfaces", "lbph",
                         "lbp_fisherfaces", "cnn")

    def validate_only(self, images: np.ndarray, labels: np.ndarray,
                      subject_names: List[str]) -> float:
        """K-fold this config on a scratch model WITHOUT the full-dataset
        fit (``train`` = this + fit; ``select_model`` scores candidates
        with this so losers never pay the fit — for the CNN backend that
        fit is the whole training run again). Returns the mean accuracy;
        ``self.validation`` holds the folds."""
        from opencv_facerecognizer_tpu.ops import image as image_ops

        images = np.asarray(images, np.float32)
        if images.shape[1:] != tuple(self.config.image_size):
            images = np.asarray(image_ops.resize(images, self.config.image_size))
        labels = np.asarray(labels, np.int32)
        scratch = self._build_model(subject_names)
        self.validation = KFoldCrossValidation(
            k=max(self.config.kfold, 2)).validate(scratch, images, labels)
        return self.mean_accuracy

    # ---- serving handoff (cnn backend) ----

    def build_gallery(self, images: np.ndarray, labels: np.ndarray, mesh,
                      capacity: int = 0, store_dtype=np.float32):
        """Embed the enrolled set with the trained CNN and install it into a
        ShardedGallery for the serving pipeline. A ``store_dtype`` that
        differs from the serving gallery's is fine for the
        ``Recognizer.reload_gallery`` handoff — ``swap_from`` casts the
        staged snapshot to the serving width at install (the default f32
        here lands in the bf16 ocvf-recognize default without the caller
        knowing serving's dtype; round-5 advisor). Pass ``jnp.bfloat16``
        to skip that one extra cast+upload when you do know it."""
        from opencv_facerecognizer_tpu.parallel.gallery import ShardedGallery

        if self.model is None or not isinstance(self.model.feature, CNNEmbedding):
            raise RuntimeError("build_gallery requires a trained cnn model")
        emb = np.array(self.model.feature.extract(np.asarray(images, np.float32)))
        capacity = capacity or max(2 * len(emb), 64)
        gallery = ShardedGallery(capacity=capacity, dim=emb.shape[1], mesh=mesh,
                                 store_dtype=store_dtype)
        gallery.add(emb, np.asarray(labels, np.int32))  # ocvf-lint: boundary=wal-before-mutate -- offline gallery BUILD from training data: the result is persisted wholesale via a checkpoint, not row-by-row enrollment; no WAL exists yet
        return gallery

    # ---- embedder evolution (the live-rollout recipe) ----

    def finetune_embedder(self, images: np.ndarray, labels: np.ndarray, *,
                          steps: int = 100, identities_per_batch: int = 8,
                          samples_per_identity: int = 4,
                          learning_rate: float = 1e-4, margin: float = 0.5,
                          scale: float = 32.0, seed: int = 0):
        """Multibatch metric-learning fine-tune (arxiv 1605.07270) of the
        trained CNN embedder on accumulated enrollments — the model half
        of a live rollout (``runtime.rollout`` owns the serving half).

        The multibatch recipe: every SGD batch samples ``k`` identities x
        ``m`` crops each, so all ``(km)² - km`` ordered pairs inside the
        batch contribute signal per step instead of the uniform sampler's
        mostly-negative pairs — the paper's variance-reduction argument,
        and the reason a few hundred steps over a small accumulated
        enrollment set moves a frozen embedder at all. Training starts
        FROM the serving model's params (a fine-tune, not a re-train) on
        a COPY: ``self.model`` — the embedder still serving the fleet —
        is never touched. Returns the fine-tuned ``CNNEmbedding``; hand
        it to ``make_reembed_fn`` + a ``RolloutCoordinator`` to roll it
        out, and roll BACK by pointing the same machinery at the old
        feature."""
        import jax
        import jax.numpy as jnp
        import optax

        from opencv_facerecognizer_tpu.models.embedder import (
            make_train_step, normalize_faces,
        )

        if self.model is None or not isinstance(self.model.feature,
                                                CNNEmbedding):
            raise RuntimeError("finetune_embedder requires a trained cnn "
                               "model (TheTrainer(model='cnn').train first)")
        old = self.model.feature
        x = np.asarray(normalize_faces(
            np.asarray(images, np.float32), old.input_size))
        y_raw = np.asarray(labels, np.int32)
        classes, y = np.unique(y_raw, return_inverse=True)
        y = y.astype(np.int32)
        # Clone the architecture; seed params from the SERVING model (a
        # deep copy — gradients must not alias the live embedder's trees).
        new_feature = CNNEmbedding(
            embed_dim=old.embed_dim, input_size=old.input_size,
            stem_features=old.stem_features,
            stage_features=old.stage_features,
            stage_blocks=old.stage_blocks, block=old.block,
            space_to_depth=old.space_to_depth, norm=old.norm,
            train_steps=0, seed=old.seed, tta=old.tta)
        params = jax.tree_util.tree_map(
            lambda a: jnp.array(np.asarray(a)), dict(old._params))
        num_classes = max(1, len(classes))
        if params["head"].shape[0] != num_classes:
            params = dict(params, head=jax.random.normal(
                jax.random.PRNGKey(seed + 1),
                (num_classes, old.embed_dim), dtype=jnp.float32))
        optimizer = optax.adam(float(learning_rate))
        opt_state = optimizer.init(params)
        step = make_train_step(old.net, optimizer, float(margin),
                               float(scale), augment=False)
        by_class = [np.flatnonzero(y == c) for c in range(num_classes)]
        k = min(int(identities_per_batch), num_classes)
        m = max(1, int(samples_per_identity))
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed)
        for i in range(int(steps)):
            # One multibatch: k identities x m samples (with replacement
            # inside an identity when it has fewer crops — small enrolled
            # subjects still contribute full positive-pair counts).
            ids = rng.choice(num_classes, size=k, replace=False)
            idx = np.concatenate([
                rng.choice(by_class[c], size=m,
                           replace=len(by_class[c]) < m) for c in ids])
            key, sub = jax.random.split(key)
            params, opt_state, _loss = step(
                params, opt_state, jnp.asarray(x[idx]), jnp.asarray(y[idx]),
                sub, jnp.float32(min(1.0, i / max(1, int(0.1 * steps)))))
        new_feature.load_params(params)
        return new_feature

    @staticmethod
    def make_reembed_fn(feature, source_images: np.ndarray):
        """The ``RolloutCoordinator.reembed_fn`` for a real fine-tuned
        embedder: re-EXTRACTS each gallery row's stored source crop with
        the new model (an embedding in one space cannot be mapped into
        another without its source — production keeps the enrollment
        crops exactly for this). ``source_images[i]`` must be row ``i``'s
        source crop, in gallery row order (append-only, like the rows).
        Deterministic over its inputs, as the stage's resume contract
        requires."""
        def reembed(rows: np.ndarray, start: int) -> np.ndarray:
            end = start + int(np.asarray(rows).shape[0])
            crops = np.asarray(source_images[start:end], np.float32)
            return np.asarray(feature.extract(crops), np.float32)

        return reembed


def select_model(
    images: np.ndarray,
    labels: np.ndarray,
    subject_names: List[str],
    candidates: Optional[Tuple[str, ...]] = None,
    model_path: Optional[str] = None,
    **config_overrides,
) -> Tuple[TheTrainer, Dict[str, float]]:
    """K-fold every candidate model kind on the SAME data and keep the
    winner: the reference workflow's 'which classic do I use?' question as
    a one-call measured answer (the round-5 LBP-Fisherfaces result showed
    the answer is dataset-dependent and guessing costs double-digit
    accuracy points).

    Each candidate scores through ``TheTrainer.validate_only`` with the
    shared ``config_overrides`` (kfold, image_size, classifier, ...); only
    the winner pays the full-dataset fit. Returns ``(winning trainer —
    trained on the full set and checkpointed to ``model_path`` if given,
    {kind: mean k-fold accuracy})``. Ties break toward the earlier
    candidate (cheaper family).
    """
    from opencv_facerecognizer_tpu.ops import image as image_ops

    candidates = tuple(candidates or TheTrainer.SELECT_CANDIDATES)
    trainers = {kind: TheTrainer(TrainerConfig(model=kind), **config_overrides)
                for kind in candidates}
    # image_size is shared (same overrides) — resize ONCE here; each
    # validate_only's internal resize then no-ops on matching shapes.
    shared_size = tuple(trainers[candidates[0]].config.image_size)
    images = np.asarray(images, np.float32)
    if images.shape[1:] != shared_size:
        images = np.asarray(image_ops.resize(images, shared_size))
    scores: Dict[str, float] = {}
    for kind in candidates:
        scores[kind] = float(trainers[kind].validate_only(
            images, labels, subject_names))
    best = max(candidates, key=lambda k: scores[k])
    winner = trainers[best]
    winner.train(images, labels, subject_names, model_path, validate=False)
    return winner, scores

"""Pipeline parallelism: detect/align and embed/match on disjoint device
subsets (SURVEY.md §2.3 "PP" row — optional in the reference mapping, built
here to complete the parallelism surface).

When to use: the fused single-graph pipeline (``parallel.pipeline``) is the
right default — one chip holds both nets comfortably and XLA fuses across
stages. PP pays off when the stages *can't* share a chip (a much larger
detector/embedder, or a gallery occupying most of HBM) or when stage
specialization beats data parallelism for a fixed chip budget.

TPU-first shape of the design:

- Stage A (detector convs + static-shape decode + matmul-form crop-resize)
  is one jitted graph pinned to ``mesh_a``; stage B (embedder + gallery
  match) is another pinned to the gallery's mesh. Each mesh is an ordinary
  (dp, tp) mesh, so stage B's gallery is still tp-sharded *within* its
  subset — PP composes with the existing axes rather than replacing them.
  Stage B's matcher comes from ``ShardedGallery.match_fn``, so the pallas
  streaming fast path applies under the same conditions as everywhere else.
- The inter-stage hop is a ``jax.device_put`` of the [B, K, fh, fw] crop
  block to stage B's shardings, plus the tiny box/score/valid arrays
  (so every result leaf lands on stage B's mesh and the packed
  single-readback path is one jit) — on hardware these are
  device-to-device ICI transfers, no host round-trip.
- Pipelining needs no threads: JAX dispatch is async, and the two graphs
  occupy disjoint devices, so issuing A(i+1) before draining B(i) overlaps
  them; ``depth=2`` software pipelining falls out of call ordering. The
  driver keeps at most one batch in each stage.
- The gallery stays LIVE: every batch reads ``gallery.data`` (the same
  atomic snapshot discipline as the fused pipeline), so enrolments and
  double-buffered swaps land on the next batch; a capacity grow re-selects
  the matcher and retraces stage B, exactly like
  ``RecognitionPipeline._step_key``.

Correctness contract: identical outputs to
``RecognitionPipeline.recognize_batch`` for the same inputs (tested on the
CPU mesh in tests/test_pp.py).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Iterator, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opencv_facerecognizer_tpu.models import embedder as embedder_mod
from opencv_facerecognizer_tpu.ops import image as image_ops
from opencv_facerecognizer_tpu.parallel.gallery import ShardedGallery
from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from opencv_facerecognizer_tpu.parallel.pipeline import (
    Detector, RecognitionResult, as_detector, pack_result,
)


def split_mesh(mesh: Mesh) -> Tuple[Mesh, Mesh]:
    """Split a (dp, tp) mesh into two equal stage meshes along dp.

    dp is the split axis because stage A has no tp dimension (detector
    params are replicated) while stage B may want every tp shard it can
    get; equal halves keep one batch size valid on both stages. Odd dp is
    rejected — unequal halves would need per-stage batch sizes (a 9-frame
    batch cannot dp-shard 2 ways on one half and 1 way on the other).
    """
    devs = mesh.devices
    dp = devs.shape[0]
    if dp < 2 or dp % 2:
        raise ValueError(
            f"PP needs an even dp >= 2 to split equally (got dp={dp}); "
            "build the mesh with make_mesh(dp=2*n) or use the fused "
            "single-mesh pipeline"
        )
    half = dp // 2
    return (Mesh(devs[:half], (DP_AXIS, TP_AXIS)),
            Mesh(devs[half:], (DP_AXIS, TP_AXIS)))


class TwoStagePipeline:
    """Detect/align on ``mesh_a``; embed/match on ``gallery.mesh``."""

    def __init__(
        self,
        detector: Detector,  # or a CNNFaceDetector: ``as_detector`` wraps it
        embed_net: embedder_mod.FaceEmbedNet,
        embed_params: Dict[str, Any],
        gallery: ShardedGallery,
        mesh_a: Mesh,
        face_size: Tuple[int, int] = (112, 112),
        top_k: int = 1,
    ):
        mesh_b = gallery.mesh
        overlap = (set(d.id for d in mesh_a.devices.flat)
                   & set(d.id for d in mesh_b.devices.flat))
        if overlap:
            raise ValueError(
                f"stage meshes share devices {sorted(overlap)}; PP requires "
                "disjoint subsets (use split_mesh, and build the gallery on "
                "the second half)"
            )
        self.detector = detector
        self.embed_net = embed_net
        # Public, mesh-agnostic copy — RecognizerService's enrolment path
        # runs the embedder host-side batches through embed_net/embed_params
        # exactly as it does with RecognitionPipeline.
        self.embed_params = embed_params
        self.gallery = gallery
        self.face_size = tuple(face_size)
        self.top_k = int(top_k)
        self.mesh_a = mesh_a
        self.mesh_b = mesh_b
        det = as_detector(detector)

        def stage_a(det_params, frames):
            frames = frames.astype(jnp.float32)  # uint8 fast-transfer path
            boxes, det_scores, valid = det.detect_traced(det_params, frames)
            crops = image_ops.batched_crop_resize(frames, boxes, face_size)
            return boxes, det_scores, valid, crops

        frames_in = NamedSharding(mesh_a, P(DP_AXIS, None, None))
        self._stage_a = jax.jit(stage_a, in_shardings=(None, frames_in))
        # Stage B input shardings for the inter-stage device_put hop.
        self._b_crops = NamedSharding(mesh_b, P(DP_AXIS, None, None, None))
        self._b_repl = NamedSharding(mesh_b, P())
        # Params are static per pipeline: pin each stage's copy to its mesh
        # once. The GALLERY is deliberately not snapshotted here — see
        # _stage_b_fn/_submit_b.
        self._emb_params = jax.device_put(embed_params, self._b_repl)
        self._det_params = jax.device_put(
            detector.params, NamedSharding(mesh_a, P())
        )
        self._b_cache: Dict[Any, Any] = {}
        self._served_crop_shapes = set()
        self._pack = jax.jit(pack_result)  # once: serving hot-loop path
        # Same off-the-serving-path warm contract as RecognitionPipeline:
        # the gallery's grow worker compiles stage B for the target tier
        # before publishing the swap, and stale tiers' executables are
        # dropped after a later grow publishes.
        gallery.prewarm_hooks.append(self.prewarm_capacity)
        gallery.evict_hooks.append(self.evict_below)

    def prewarm_capacity(self, capacity: int) -> None:
        """Compile stage B for a FUTURE gallery capacity (grow-worker
        thread): build the stage-B jit for the target (capacity, pallas)
        key and force its compile with zero-filled scratch arrays at every
        crop shape already served."""
        g = self.gallery
        key = (capacity, g._pallas_enabled(capacity))
        if key in self._b_cache:
            fn = self._b_cache[key]
        else:
            match = g.match_fn(self.top_k, capacity)
            embed_net = self.embed_net
            face_size = self.face_size
            k = self.top_k

            def stage_b(emb_params, g_emb, g_valid, g_labels, crops):
                b, kf = crops.shape[0], crops.shape[1]
                flat = crops.reshape((b * kf, *face_size))
                emb = embed_net.apply(
                    {"params": emb_params},
                    embedder_mod.normalize_faces(flat, face_size),
                )
                labels, sims, _ = match(emb, g_emb, g_valid, g_labels)
                return labels.reshape((b, kf, k)), sims.reshape((b, kf, k))

            fn = self._b_cache[key] = jax.jit(stage_b)
        served_shapes = set(self._served_crop_shapes)
        if not served_shapes:
            return
        # store_dtype, not f32: aval mismatch would nullify the warm
        # (see pipeline.prewarm_capacity).
        scratch_emb = jax.device_put(
            jnp.zeros((capacity, g.dim), g.store_dtype), g._emb_sharding
        )
        scratch_lab = jax.device_put(
            jnp.full((capacity,), g.labels_pad, jnp.int32), g._lab_sharding
        )
        scratch_val = jax.device_put(
            jnp.zeros((capacity,), bool), g._valid_sharding
        )
        for crop_shape in served_shapes:
            crops = jax.device_put(jnp.zeros(crop_shape, jnp.float32),
                                   self._b_crops)
            out = fn(self._emb_params, scratch_emb, scratch_val, scratch_lab,
                     crops)
            jax.block_until_ready(out)

    def _stage_b_fn(self, data):
        """Compiled stage B for the given snapshot's capacity/matcher —
        auto-grow changes both, so key the cache like
        ``RecognitionPipeline._step_key`` does, deriving capacity from the
        SAME GalleryData snapshot the call will feed (a separate
        ``gallery.capacity`` read could straddle a concurrent grow
        install and pair a stale key with new-tier arrays)."""
        capacity = data.capacity
        key = (capacity, self.gallery._pallas_enabled(capacity))
        fn = self._b_cache.get(key)  # fetch once (evict race)
        if fn is None:
            match = self.gallery.match_fn(self.top_k, capacity)
            embed_net = self.embed_net
            face_size = self.face_size
            k = self.top_k

            def stage_b(emb_params, g_emb, g_valid, g_labels, crops):
                b, kf = crops.shape[0], crops.shape[1]
                flat = crops.reshape((b * kf, *face_size))
                emb = embed_net.apply(
                    {"params": emb_params},
                    embedder_mod.normalize_faces(flat, face_size),
                )
                labels, sims, _ = match(emb, g_emb, g_valid, g_labels)
                return labels.reshape((b, kf, k)), sims.reshape((b, kf, k))

            fn = self._b_cache[key] = jax.jit(stage_b)
        return fn

    def evict_below(self, min_capacity: int) -> None:
        """Drop stage-B executables for gallery tiers strictly below
        ``min_capacity`` (see ``ShardedGallery.evict_hooks``)."""
        for key in [k for k in list(self._b_cache) if k[0] < min_capacity]:
            self._b_cache.pop(key, None)

    def _submit_a(self, frames):
        frames = jnp.asarray(frames)
        if frames.dtype != jnp.uint8:  # uint8 rides H2D as-is, cast in-graph
            frames = frames.astype(jnp.float32)
        return self._stage_a(self._det_params, frames)

    def _hop(self, a_out):
        boxes, det_scores, valid, crops = a_out
        # One D2D transfer of the stage boundary to mesh_b's shardings.
        # The per-slot arrays are tiny ([B, K, 4] and smaller); moving them
        # too keeps every result leaf on mesh_b, so the packed single-
        # readback path can fuse them in one jit.
        crops_b = jax.device_put(crops, self._b_crops)
        boxes, det_scores, valid = jax.device_put(
            (boxes, det_scores, valid), self._b_repl
        )
        return boxes, det_scores, valid, crops_b

    def _submit_b(self, hopped):
        boxes, det_scores, valid, crops_b = hopped
        self._served_crop_shapes.add(tuple(crops_b.shape))
        data = self.gallery.data  # one atomic snapshot per batch (live)
        labels, sims = self._stage_b_fn(data)(
            self._emb_params, data.embeddings, data.valid, data.labels,
            crops_b,
        )
        return RecognitionResult(
            boxes=boxes, det_scores=det_scores, valid=valid,
            labels=labels, similarities=sims,
        )

    def recognize_batch(self, frames) -> RecognitionResult:
        """Single-batch convenience path (no overlap)."""
        return self._submit_b(self._hop(self._submit_a(frames)))

    def recognize_batch_packed(self, frames) -> jnp.ndarray:
        """One packed [B, K, 6 + 2k] output array (see
        ``pipeline.pack_result``) — makes PP a drop-in pipeline for
        ``runtime.recognizer.RecognizerService``, whose serving loop does
        exactly one device->host readback per batch."""
        result = self.recognize_batch(frames)
        return self._pack(result)

    def recognize_stream(
        self, frame_batches: Iterable[Any]
    ) -> Iterator[RecognitionResult]:
        """Depth-2 pipelined stream: stage A works on batch i+1 while stage
        B works on batch i — overlap comes from async dispatch onto
        disjoint devices, not from host threads."""
        in_flight = None
        for frames in frame_batches:
            hopped = self._hop(self._submit_a(frames))
            if in_flight is not None:
                yield in_flight
            in_flight = self._submit_b(hopped)
        if in_flight is not None:
            yield in_flight

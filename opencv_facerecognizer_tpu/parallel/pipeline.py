"""The fused serving graph: detect -> align -> embed -> match as ONE jitted,
mesh-sharded call per frame batch (BASELINE.json:5: "detect->align->embed->
match executes as one pmap'd call per batch"; SURVEY.md §3.3 rebuild note).

Static-shape discipline end-to-end (SURVEY.md §7 "hard parts"): every frame
contributes exactly ``max_faces`` slots; empty slots ride along as invalid
(masked) work. TPUs vastly prefer predictable dense compute over dynamic
shapes — invalid-slot embeddings are garbage lanes of a batched matmul, not
wasted recompiles.

Sharding: frames are dp-sharded and replicated over tp; detector, gate and
embedder params are replicated (placed on every chip of the mesh once, not
per call); the gallery match inside is tp-sharded (see
``parallel.gallery``): every chip of a tp group runs detect, crop and
embed on the whole batch and searches its own shard of the gallery, and
the packed result comes back replicated and is read from one chip. XLA
inserts the collectives; nothing here names a wire protocol.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from opencv_facerecognizer_tpu.models import detector as detector_mod
from opencv_facerecognizer_tpu.models import embedder as embedder_mod
from opencv_facerecognizer_tpu.ops import image as image_ops
from opencv_facerecognizer_tpu.parallel.gallery import ShardedGallery
from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS


class EmbedNet(Protocol):
    """What the step needs of an embedder: a flax-style ``apply`` taking
    ``{"params": ...}`` and [N, h, w] standardized crops, giving [N, E]
    unit rows (``FaceEmbedNet``, ``IResNet``, ``ViT``). A net may name
    scopes of its own inside the step's ``ocvf_embed``, under names the
    trace reader's ``ocvf_<stage>`` does not match (``vit_attn``). For the
    dispatch's provenance a net may state ``feature_name`` (the name of the
    feature class that owns it), where a crop becomes tokens
    ``tokens(face_size)`` and, where its attention has a kernel that only
    some lowerings reach, ``attention_kernel``: the custom call's name."""

    def apply(self, variables: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray: ...


class Detector(Protocol):
    """What the step needs of a detector, whatever its head: how many face
    slots a frame gets, the parameters (a jit ARGUMENT of every step, so a
    swap keeps the executables warm) and one function to trace,
    ``(params, float32 frames [B, H, W]) -> (boxes [B, K, 4] pixel yxyx,
    scores [B, K], valid [B, K])``. The function names its own scopes:
    ``ocvf_detect`` round the net's forward and, where the decode is work
    of its own, a SIBLING ``ocvf_decode`` (the trace reader files an
    operation under the first ``ocvf_<stage>`` of its ``tf_op``).
    ``models.scrfd.SCRFDDetector`` is one; ``as_detector`` makes one of a
    ``CNNFaceDetector``."""

    kind: str
    max_faces: int

    @property
    def params(self) -> Any: ...

    def detect_traced(self, params: Any, frames: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]: ...


class HeatmapDetector:
    """``CNNFaceDetector`` behind the ``Detector`` boundary (the class
    itself stays as the committed nets' recipe hashed it): the stride-8
    centre-heatmap net and its decode, both under ``ocvf_detect`` as they
    have been since the scope was named."""

    kind = "heatmap"

    def __init__(self, detector: detector_mod.CNNFaceDetector):
        self.wrapped = detector

    @property
    def max_faces(self) -> int:
        return self.wrapped.max_faces

    @property
    def params(self):
        return self.wrapped.params

    def detect_traced(self, params, frames):
        det = self.wrapped
        with jax.named_scope("ocvf_detect"):
            outputs = det.net.apply({"params": params}, frames)
            return detector_mod.decode_detections(
                outputs, det.max_faces, det.score_threshold, det.iou_threshold)


def as_detector(detector) -> Detector:
    """``detector`` as the steps take it: a ``CNNFaceDetector`` wrapped,
    anything that already traces its own detection as it is."""
    if isinstance(detector, detector_mod.CNNFaceDetector):
        return HeatmapDetector(detector)
    return detector


class RecognitionResult(NamedTuple):
    boxes: jnp.ndarray  # [B, K, 4] pixel yxyx
    det_scores: jnp.ndarray  # [B, K]
    valid: jnp.ndarray  # [B, K] bool
    labels: jnp.ndarray  # [B, K, k] gallery labels, best first
    similarities: jnp.ndarray  # [B, K, k] cosine similarity


def pack_result(result: "RecognitionResult") -> jnp.ndarray:
    """[B, K, 6 + 2k] int32, thirty-two bits a lane: boxes | det_score |
    valid | labels | sims.

    One output array instead of five, so the serving loop issues exactly
    one device->host readback per batch (one transfer to wait on, one
    array to hand the readback worker). Whether five small readbacks cost
    measurably more than one on the locally attached chip: not measured.
    The lanes are INTEGER: labels ride as they are, exact whatever their
    value (as a float32 VALUE a label rounds from 2^24 on, and a watchlist
    of 50 M rows labels past that), and the floats ride as their bits, a
    bitcast. Not the other way round: float lanes carrying a label's bits
    lose every label under 2^23, whose bits are a denormal float that the
    TPU flushes to zero on the way through the concatenation (read on
    four v5e chips, PR 38: subject 1 was published as subject 0).
    """
    def bits(x):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)

    return jnp.concatenate([
        bits(result.boxes),
        bits(result.det_scores)[..., None],
        result.valid[..., None].astype(jnp.int32),
        result.labels.astype(jnp.int32),
        bits(result.similarities),
    ], axis=-1)


def unpack_result(packed: np.ndarray, top_k: int) -> RecognitionResult:
    """Host-side inverse of ``pack_result`` (numpy views, no copies)."""
    floats = packed.view(np.float32)
    return RecognitionResult(
        boxes=floats[..., 0:4],
        det_scores=floats[..., 4],
        valid=packed[..., 5] != 0,
        labels=packed[..., 6:6 + top_k],
        similarities=floats[..., 6 + top_k:6 + 2 * top_k],
    )


class RecognitionPipeline:
    """Holds the nets + gallery and compiles the fused per-batch step."""

    def __init__(
        self,
        detector: "Detector | detector_mod.CNNFaceDetector",
        embed_net: EmbedNet,
        embed_params: Dict[str, Any],
        gallery: ShardedGallery,
        face_size: Tuple[int, int] = (112, 112),
        top_k: int = 1,
        donate_frames: bool = False,
        cascade=None,
    ):
        self.detector = detector
        #: the detector's kind, for the dispatch's provenance (the object is
        #: never replaced: a registry swap loads parameters into it)
        self._detector_kind = as_detector(detector).kind
        self.embed_net = embed_net
        self.face_size = tuple(face_size)
        #: the embedder's kind and the tokens it makes of a crop (0: the net
        #: has no token axis), for the dispatch's provenance.
        #: ``FaceEmbedNet``'s file is hashed into the committed nets' recipe
        #: and names no feature class: a net that names none is its
        self._embedder_kind = getattr(embed_net, "feature_name",
                                      embedder_mod.CNNEmbedding.name)
        self._tokens_per_slot = (int(embed_net.tokens(self.face_size))
                                 if hasattr(embed_net, "tokens") else 0)
        #: the custom call a net's attention lowers to where its kernel is on
        #: the path (None: the net states none), and face slots of a step ->
        #: whether the text the step lowered to holds it
        self._attention_kernel = getattr(embed_net, "attention_kernel", None)
        self._attention_forms: Dict[int, str] = {}
        self.gallery = gallery
        mesh = gallery.mesh
        #: where a batch of frames lives: dp-sharded, on every chip of a tp
        #: group. The serving loop uploads straight to it on a mesh of
        #: several chips (``runtime.recognizer``).
        self.frames_sharding = NamedSharding(mesh, P(DP_AXIS, None, None))
        self._replicated = NamedSharding(mesh, P())
        self.embed_params = embed_params
        # Net parameters are jit ARGUMENTS of every step. On a mesh of
        # several chips each call would copy them to every chip over
        # again, so ``_placed`` keeps one replicated copy of each tree,
        # made again when the tree is swapped (a registry install); the
        # objects that own the trees are left as they are.
        self._placed_trees: Dict[str, Tuple[Any, Any]] = {}
        self.top_k = int(top_k)
        # Stage-1 detection cascade (models.cascade.FaceGate): when set,
        # the serving runtime scores every batch with ``cascade_scores``
        # first and only survivors reach the fused detect->crop->embed->
        # match step — rejected frames settle as ``completed_empty`` in
        # the admission ledger (runtime/recognizer.py owns the decision;
        # this object only holds the compiled per-rung stage-1 pass).
        self.cascade = cascade
        # Donate the frames argument of the PACKED serving step through
        # the whole bucketed ladder: the ingest uploader ships each batch
        # as its own fresh device array (uint8, one device_put per
        # dispatch attempt), so XLA may reuse that buffer's memory for
        # outputs instead of allocating. Only flip this on backends that
        # implement input donation (TPU/GPU — CPU ignores it with a
        # warning) AND when every caller routes through the uploader:
        # a donated array must never be re-fed after dispatch.
        self.donate_frames = bool(donate_frames)
        # Chaos hook (runtime.faults.FaultInjector): checked at the device-
        # dispatch boundary of both recognize paths, so an injected
        # UNAVAILABLE surfaces exactly where the real backend's fast-fail
        # outage does — inside the serving loop's dispatch try-block, after
        # batching and before any readback. None (production) costs one
        # attribute test per batch. RecognizerService installs/uninstalls
        # it around its start/stop so a shared pipeline never leaks faults
        # into the next service built on it.
        self.fault_injector = None
        # keyed by _step_key: (batch, h, w, dtype_str, capacity, pallas)
        self._step_cache: Dict[Tuple, Any] = {}
        self._packed_cache: Dict[Tuple, Any] = {}
        # Stage-1 cascade executables, keyed (batch, h, w, dtype_str):
        # gallery capacity never enters the stage-1 graph, so grows and
        # quantizer churn leave these warm.
        self._cascade_cache: Dict[Tuple, Any] = {}
        # Register with the gallery's async-grow machinery: when a grow is
        # imminent/in flight, the worker thread compiles THIS pipeline's
        # step for the target capacity before the swap is published, so
        # the serving thread's first call at the new tier finds a warm
        # cache instead of paying the XLA recompile (SURVEY.md §5.3) —
        # and after a later grow publishes, stale tiers' executables are
        # dropped (evict_hooks) instead of accumulating forever.
        gallery.prewarm_hooks.append(self.prewarm_capacity)
        gallery.evict_hooks.append(self.evict_below)

    def _placed(self, name: str, tree):
        """``tree`` as the steps take it: on a mesh of several chips a
        copy on every one of them, kept until ``tree`` is another object;
        on one device the tree itself."""
        if self.gallery.mesh.size == 1:
            return tree
        kept = self._placed_trees.get(name)
        if kept is None or kept[0] is not tree:
            kept = self._placed_trees[name] = (
                tree, jax.device_put(tree, self._replicated))
        return kept[1]

    def _net_params(self):
        return (self._placed("detector", self.detector.params),
                self._placed("embedder", self.embed_params))

    def _build_step(self, batch: int, height: int, width: int,
                    capacity: Optional[int] = None, use_ivf: bool = False):
        det = as_detector(self.detector)
        k = self.top_k
        face_size = self.face_size
        embed_net = self.embed_net
        max_faces = det.max_faces
        # The gallery owns matcher selection (two-stage ivf vs pallas
        # streaming vs GSPMD global view) — the fused step inherits
        # whichever fits the mesh and capacity; _step_key re-selects if
        # the gallery grows or the quantizer (in)validates, and prewarm
        # passes the FUTURE capacity explicitly. ``use_ivf`` is pinned by
        # the caller's snapshot so a concurrent quantizer flip can't
        # change the match arity mid-build.
        match = self.gallery.match_fn(k, capacity, use_ivf=use_ivf)

        def step(det_params, emb_params, gallery_emb, gallery_valid,
                 gallery_labels, frames, ivf=()):
            # Camera frames ride host->device as uint8 when the caller has
            # them that way (4x fewer host->device bytes than f32); the cast
            # to f32 happens here, on device.
            frames = frames.astype(jnp.float32)
            # 1) detect (dense convs; dp-sharded batch): the detector's own
            # traced function, which names ``ocvf_detect`` (and, where its
            # decode is work of its own, ``ocvf_decode``) itself
            boxes, det_scores, valid = det.detect_traced(det_params, frames)
            # 2) align: dynamic crop+resize and per-crop standardization,
            # all slots (invalid ones too)
            with jax.named_scope("ocvf_crop"):
                crops = image_ops.batched_crop_resize(frames, boxes, face_size)
                flat = embedder_mod.normalize_faces(
                    crops.reshape((batch * max_faces, *face_size)), face_size)
            # 3) embed (the flax graph of either feature class)
            with jax.named_scope("ocvf_embed"):
                emb = embed_net.apply({"params": emb_params}, flat)  # [B*K, E] unit-norm
            # 4) match against the gallery (selection in gallery.match_fn:
            # two-stage ivf for a ready quantizer above its threshold,
            # pallas streaming on one chip or on every shard, GSPMD global
            # view for CPU meshes and small shards). The function names
            # its own scopes: ``ocvf_match`` round the search and, beside
            # it, ``ocvf_merge`` round what makes one answer of the shards'.
            if use_ivf:
                labels, sims, _ = match(
                    emb, gallery_emb, gallery_valid, gallery_labels, ivf
                )
            else:
                labels, sims, _ = match(
                    emb, gallery_emb, gallery_valid, gallery_labels
                )
            return RecognitionResult(
                boxes=boxes,
                det_scores=det_scores,
                valid=valid,
                labels=labels.reshape((batch, max_faces, k)),
                similarities=sims.reshape((batch, max_faces, k)),
            )

        # ocvf-lint: boundary=jit-recompile-hazard -- THE cache-keyed builder: every serving call reaches this jit only through _step_cache misses, and warmup/prewarm compile every ladder bucket + future tier up front
        return jax.jit(step, in_shardings=(None, None, None, None, None,
                                           self.frames_sharding, None))

    def _step_key(self, frames: jnp.ndarray, data, ivf=None) -> Tuple:
        # Gallery capacity (and with it the pallas/GSPMD/ivf selection)
        # can change at runtime via auto-grow — bake both into the cache
        # key so a grown gallery re-selects its matcher instead of
        # re-tracing the old closure at the new shapes. All derive from
        # the SAME GalleryData/IVFDeviceData snapshots the call will
        # feed: reading ``gallery.capacity`` separately could pair a
        # stale key with new-tier arrays across a concurrent grow
        # install, forcing the retrace (and, with GSPMD at 1M rows, the
        # [Q, capacity] HBM materialization) that prewarm exists to
        # avoid. Input dtype is a trace shape too (uint8 fast transfer
        # vs f32). The ivf signature is the quantizer's static shapes —
        # a same-shape retrain republish reuses the compiled step.
        capacity = data.capacity
        return (*frames.shape, str(frames.dtype), capacity,
                self.gallery._pallas_enabled(capacity),
                None if ivf is None else ivf.shape_signature())

    def _as_device_frames(self, frames) -> jnp.ndarray:
        """uint8 stays uint8 (fast H2D path — cast happens in-graph);
        everything else normalizes to f32. On a mesh of several chips host
        frames go straight to the step's placement, not by way of the
        default device."""
        if self.gallery.mesh.size > 1 and not isinstance(frames, jax.Array):
            frames = np.asarray(frames)
            if frames.dtype != np.uint8:
                frames = frames.astype(np.float32)
            return jax.device_put(frames, self.frames_sharding)
        frames = jnp.asarray(frames)
        if frames.dtype != jnp.uint8:
            frames = frames.astype(jnp.float32)
        return frames

    def recognize_batch(self, frames: jnp.ndarray) -> RecognitionResult:
        """[B, H, W] frames (f32 or uint8) -> RecognitionResult; B must
        divide by dp size, and B * max_faces must too (it does when B
        does)."""
        if self.fault_injector is not None:
            self.fault_injector.on_dispatch()
        frames = self._as_device_frames(frames)
        data = self.gallery.data  # one atomic snapshot (see GalleryData)
        ivf = self.gallery._ivf_data(data)  # one epoch-checked quantizer read
        key = self._step_key(frames, data, ivf)
        # Fetch ONCE and hold the reference: a concurrent double-grow can
        # evict this tier's entry between a membership check and a second
        # subscript (evict_below runs on the grow worker).
        step = self._step_cache.get(key)
        if step is None:
            self._evict_stale_ivf(key)
            step = self._step_cache[key] = self._build_step(
                *frames.shape, capacity=data.capacity,
                use_ivf=ivf is not None)
        return step(
            *self._net_params(),
            data.embeddings,
            data.valid,
            data.labels,
            frames,
            ivf if ivf is not None else (),
        )

    def recognize_batch_packed(self, frames: jnp.ndarray) -> jnp.ndarray:
        """Same fused step, but the outputs leave the device as ONE packed
        [B, K, 6 + 2k] int32 array (see ``pack_result``) — the serving loop's
        single-readback path. Decode host-side with ``unpack_result``."""
        if self.fault_injector is not None:
            self.fault_injector.on_dispatch()
        frames = self._as_device_frames(frames)
        data = self.gallery.data  # one atomic snapshot (see GalleryData)
        ivf = self.gallery._ivf_data(data)  # one epoch-checked quantizer read
        key = self._step_key(frames, data, ivf)
        packed = self._packed_cache.get(key)  # fetch once (evict race)
        # Host-side dispatch provenance for the frame-lifecycle tracer's
        # batch spans (runtime.recognizer reads it right after the call):
        # plain attr store, best-effort — informational, never synchronized.
        # ``detect_frames``: frames this step sends through the detector
        # (the whole rung); ``embed_slots``: face slots it sends through the
        # embedder (every frame of the rung carries max_faces, valid or
        # not); ``detector`` / ``embedder``: the kind of each the step
        # traced; ``embed_tokens``: slots x the tokens a crop becomes, only
        # for an embedder that has a token axis; ``embed_attention``: the
        # form its attention lowered to in this step (``"kernel"`` or
        # ``"xla"``), only for an embedder that states a kernel.
        slots = int(frames.shape[0]) * int(self.detector.max_faces)
        self.last_dispatch_info = {
            "cache_hit": packed is not None,
            "mode": "ivf" if ivf is not None else "exact",
            "detector": self._detector_kind,
            "embedder": self._embedder_kind,
            "detect_frames": int(frames.shape[0]),
            "embed_slots": slots}
        if self._tokens_per_slot:
            self.last_dispatch_info["embed_tokens"] = slots * self._tokens_per_slot
        if packed is None:
            self._evict_stale_ivf(key)
            step = self._step_cache.get(key)
            if step is None:
                step = self._step_cache[key] = self._build_step(
                    *frames.shape, capacity=data.capacity,
                    use_ivf=ivf is not None)

            def packed_step(det_p, emb_p, g_emb, g_valid, g_lab, fr, iv):
                return pack_result(step(det_p, emb_p, g_emb, g_valid,
                                        g_lab, fr, iv))

            packed = self._packed_cache[key] = jax.jit(  # ocvf-lint: boundary=jit-recompile-hazard -- packed-cache fill: warmup compiles every dispatch bucket, so serving only lands here on a genuinely new (shape, capacity, matcher) key
                packed_step, out_shardings=self.frames_sharding,
                donate_argnums=(5,) if self.donate_frames else ())
        args = (*self._net_params(), data.embeddings, data.valid, data.labels,
                frames, ivf if ivf is not None else ())
        if self._attention_kernel:
            form = self._attention_forms.get(slots)
            if form is None:
                # once a number of slots, observed and not inferred from the
                # platform: whether the text this step lowers to for its
                # devices holds the kernel's custom call (the trace is the
                # one the call below uses)
                text = packed.lower(*args).as_text()
                form = self._attention_forms[slots] = (
                    "kernel" if self._attention_kernel in text else "xla")
            self.last_dispatch_info["embed_attention"] = form
        return packed(*args)

    def lower_packed(self, batch: int, height: int, width: int, dtype):
        """``jax.stages.Lowered`` of the packed serving step cached for
        this (batch, frame, dtype) at the CURRENT gallery/quantizer
        snapshot — what a dispatch of that shape would run. For
        inspection (``chip_smoke.py`` reads the lowered text to observe
        that the matcher lowered to a Mosaic custom call rather than
        inferring it from the platform); raises ``KeyError`` when that
        shape was never warmed."""
        frames = jax.ShapeDtypeStruct((batch, height, width), np.dtype(dtype))
        data = self.gallery.data
        ivf = self.gallery._ivf_data(data)
        packed = self._packed_cache[self._step_key(frames, data, ivf)]
        return packed.lower(
            *self._net_params(), data.embeddings,
            data.valid, data.labels, frames, ivf if ivf is not None else ())

    def cascade_scores(self, frames) -> jnp.ndarray:
        """Compiled stage-1 pass: [B, H, W] frames (f32 or uint8) -> [B]
        face-possible probabilities on device. Cache-keyed per
        (shape, dtype) exactly like the serving steps, so every dispatch
        rung the warmup prewarmed is a jit-cache hit — the recompile
        watchdog reads ``last_cascade_info`` the way it reads
        ``last_dispatch_info`` for stage 2. The caller (the serving
        loop's cascade gate) materializes the tiny [B] result; that one
        readback IS the early-exit decision point."""
        from opencv_facerecognizer_tpu.models import cascade as cascade_mod

        gate = self.cascade
        if gate is None:
            raise RuntimeError("cascade_scores called with no cascade gate")
        frames = self._as_device_frames(frames)
        key = (*frames.shape, str(frames.dtype))
        fn = self._cascade_cache.get(key)
        # Host-side provenance for the recompile watchdog (mirrors
        # last_dispatch_info: plain attr store, informational only).
        self.last_cascade_info = {"cache_hit": fn is not None}
        if fn is None:
            net = gate.net

            def gate_stage1(params, fr):
                # uint8 ingest frames cast on device, like the fused step.
                return cascade_mod.frame_scores(net, params,
                                                fr.astype(jnp.float32))

            # The closure's name is the program's on the device trace's
            # "XLA Modules" line (``jit_gate_stage1``), where device time
            # per program is read: rename it and those readers go blind.
            # On the step's mesh, frames placed as the step takes them:
            # every chip of a tp group scores the batch, so the readback
            # waits on the same queues the step does.
            fn = self._cascade_cache[key] = jax.jit(  # ocvf-lint: boundary=jit-recompile-hazard -- cache-keyed stage-1 builder: warmup compiles every (rung, ingest dtype) signature up front; serving lands here only on a genuinely new shape
                gate_stage1,
                in_shardings=(self._replicated, self.frames_sharding),
                out_shardings=NamedSharding(self.gallery.mesh, P(DP_AXIS)))
        return fn(self._placed("gate", gate.params), frames)

    # ---- model-registry installs (runtime.registry swaps) ----

    def install_detector_params(self, params) -> None:
        """Publish new detector params in place (a registry detector
        swap's ``install_fn``). Detector params are jit ARGUMENTS of
        every compiled step — ``step(self.detector.params, ...)`` — so a
        same-architecture swap is one attribute store: every cached
        executable in ``_step_cache``/``_packed_cache`` stays warm and
        the very next dispatch runs the new model. Architecture changes
        do NOT go through here (they would need a new ``DetectorNet``
        and a ladder re-prewarm); the registry coordinator stages those
        as a new detector object + explicit prewarm instead."""
        self.detector.load_params(params)

    def install_cascade(self, gate) -> None:
        """Swap the stage-1 cascade gate (a registry cascade swap's
        ``install_fn``). ``cascade_scores`` reads ``self.cascade`` fresh
        per call and passes ``gate.params`` as a jit argument, so a
        same-architecture swap keeps every cached stage-1 executable
        warm. The cached closures DO hold the net object from fill time,
        so when the new gate's architecture differs (features /
        downsample) the stale executables are dropped — the next call
        per rung recompiles, which is exactly why same-config swaps are
        the supported zero-recompile path."""
        old = self.cascade
        self.cascade = gate
        if (old is None or gate is None
                or tuple(old.net.features) != tuple(gate.net.features)
                or int(old.net.downsample) != int(gate.net.downsample)):
            self._cascade_cache.clear()

    def prewarm_batch_shapes(self, batch_sizes, frame_shape,
                             dtype=np.float32) -> int:
        """Compile the packed serving step for every dispatch-bucket size
        up front (RecognizerService.warmup calls this with its bucket
        ladder): the whole point of the fixed ladder is that a partial
        batch sliced to ANY bucket finds a warm executable in
        ``_packed_cache`` instead of paying a mid-serving XLA compile.
        Each size is executed once on zero frames and blocked on, exactly
        like ``prewarm_capacity`` does for future gallery tiers. Returns
        the number of sizes compiled."""
        built = 0
        for b in sorted({int(x) for x in batch_sizes}):
            zeros = np.zeros((b, *tuple(frame_shape)), dtype)
            out = self.recognize_batch_packed(zeros)
            if hasattr(out, "block_until_ready"):
                out.block_until_ready()  # ocvf-lint: boundary=host-sync -- warmup runs BEFORE serving starts; blocking here is the point (compiles must land before the first real frame)
            if self.cascade is not None:
                # BOTH cascade stages warm per rung (and per ingest
                # dtype — the caller passes the batcher's staging dtype):
                # a mid-serving stage-1 compile would trip the same
                # recompile watchdog the ladder prewarm exists to keep
                # green.
                scores = self.cascade_scores(zeros)
                if hasattr(scores, "block_until_ready"):
                    scores.block_until_ready()  # ocvf-lint: boundary=host-sync -- warmup precedes serving; the stage-1 compile must land with the ladder's
            built += 1
        return built

    def prewarm_capacity(self, capacity: int) -> None:
        """Compile this pipeline's step(s) for a FUTURE gallery capacity.

        Called on the gallery's grow-worker thread (never the serving
        thread) for every frame-shape/dtype the pipeline has already
        served. Compilation is forced by executing each newly built step
        once against zero-filled scratch gallery arrays of the target
        tier; the jit executables land in the same function caches the
        serving thread will hit after the swap (``_step_key`` includes
        capacity + matcher selection, so the entries are keyed exactly as
        the post-grow lookups). BOTH paths are executed — the packed
        single-readback step and the unpacked ``recognize_batch`` step are
        separate XLA executables, so warming only one would leave the
        other's first post-grow call paying the full compile. Scratch
        arrays are dropped afterwards — only the executables persist.
        """
        g = self.gallery
        pallas = g._pallas_enabled(capacity)
        # Warm the EXACT-arity step for the future tier, never the ivf
        # one: prewarm's only consumers are the grow worker and the
        # early-warm thread, and the grow SPLICE invalidates the
        # quantizer (gallery._grow_worker) — so the first post-swap
        # lookup is always (ivf_sig=None, exact). Warming at the current
        # ivf signature would compile a step the swap can never hit
        # while the real post-swap key misses cold on the serving
        # thread. (The retrain that later re-enables ivf republishes
        # with fresh list shapes; its first serving call does pay a
        # compile — a known, bounded cost every first ivf enablement
        # shares, separate from the grow path this warms.)
        ivf = None
        ivf_sig = None
        served = {
            (key[0], key[1], key[2], key[3])
            for key in list(self._packed_cache) + list(self._step_cache)
        }
        if not served:
            return
        # Scratch in the gallery's own store_dtype and shardings, made on
        # the chips shard by shard: an f32 scratch on a bf16 gallery warms
        # an executable serving never hits (aval mismatch -> full retrace
        # on the serving thread post-grow).
        scratch_emb, scratch_lab, scratch_val = g._empty_arrays(capacity)
        for batch, height, width, dtype in served:
            new_key = (batch, height, width, dtype, capacity, pallas, ivf_sig)
            if new_key in self._packed_cache:
                continue
            step = self._step_cache.get(new_key)
            if step is None:
                step = self._build_step(batch, height, width, capacity,
                                        use_ivf=ivf is not None)
                self._step_cache[new_key] = step
            # placed as a served batch is, or the serving call retraces
            frames = self._as_device_frames(
                np.zeros((batch, height, width), dtype=dtype))
            ivf_arg = ivf if ivf is not None else ()
            # Execute each once: jit compiles per concrete shape; block so
            # the caller (grow worker) only installs AFTER compiles landed.
            # ocvf-lint: boundary=host-sync -- prewarm runs on the gallery's grow-worker thread, never the serving loop; the block IS the contract (install only after compiles landed)
            jax.block_until_ready(step(
                *self._net_params(),
                scratch_emb, scratch_val, scratch_lab, frames, ivf_arg,
            ))

            def packed_step(det_p, emb_p, g_emb, g_valid, g_lab, fr, iv,
                            _step=step):
                return pack_result(_step(det_p, emb_p, g_emb, g_valid,
                                         g_lab, fr, iv))

            packed = jax.jit(  # ocvf-lint: boundary=jit-recompile-hazard -- prewarm builder on the grow-worker thread: compiles the future tier so the serving thread never does
                packed_step, out_shardings=self.frames_sharding,
                donate_argnums=(5,) if self.donate_frames else ())
            packed(  # ocvf-lint: boundary=host-sync -- prewarm executes+blocks off the serving loop; install happens only after the compile landed
                *self._net_params(),
                scratch_emb, scratch_val, scratch_lab, frames, ivf_arg,
            ).block_until_ready()
            self._packed_cache[new_key] = packed

    def _evict_stale_ivf(self, key: Tuple) -> None:
        """Purge cached steps whose ivf shape signature was superseded by
        a retrain at the same (batch, frame, capacity, pallas) — the
        capacity-threshold eviction (``evict_below``) never sees
        same-capacity signature churn, so without this every staleness
        retrain would leak compiled executables for the process lifetime.
        In-flight calls already hold their function references."""
        sig = key[6]
        if sig is None:
            return
        for cache in (self._step_cache, self._packed_cache):
            for stale in [k2 for k2 in list(cache)
                          if k2[:6] == key[:6] and k2[6] not in (None, sig)]:
                cache.pop(stale, None)

    def evict_below(self, min_capacity: int) -> None:
        """Drop compiled steps for gallery tiers strictly below
        ``min_capacity`` (called from the gallery after a later grow
        publishes — see ``ShardedGallery.evict_hooks``). In-flight calls
        already hold their function references; only the cache forgets."""
        for cache in (self._step_cache, self._packed_cache):
            for key in [k for k in list(cache) if k[4] < min_capacity]:
                cache.pop(key, None)

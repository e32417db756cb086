"""The system under test for configurations whose ``"stack"`` is
``"recognize"``: the serving stack exactly as ``ocvf-recognize`` builds it
(``apps.recognize._load_stack`` + ``build_service``), on one device.

From the program this module takes the stack and its service, nothing
else. What it adds is the benchmark's own: the nets' training recipe and
their file, the enrolled subjects' images, the gallery rows made on the
device from the seed, and two observation hooks (a batch popped, a result
published).

A configuration with another kind of stack brings its own module under
``benchmark/stacks/`` and names it in its file.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark import render

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

#: program sources whose change can change what training produces
RECIPE_SOURCES = (
    "opencv_facerecognizer_tpu.models.detector",
    "opencv_facerecognizer_tpu.models.cascade",
    "opencv_facerecognizer_tpu.models.embedder",
    "opencv_facerecognizer_tpu.runtime.trainer",
    "opencv_facerecognizer_tpu.apps.train",
    "benchmark.render",
)
NET_FILES = ("detector.ckpt", "cascade.ckpt", "embedder.ckpt")


def work_dir() -> str:
    """Run-time files (generated images, nets trained here): inside the
    checkout, listed in .gitignore."""
    path = os.path.join(ROOT, ".bench_work")
    os.makedirs(path, exist_ok=True)
    return path


def _write_pgm(path: str, image: np.ndarray) -> None:
    h, w = image.shape
    with open(path, "wb") as fh:
        fh.write(f"P5\n{w} {h}\n255\n".encode("ascii"))
        fh.write(np.clip(image, 0, 255).astype(np.uint8).tobytes())


def _write_subjects(path: str, images: Dict[str, np.ndarray]) -> None:
    if os.path.isdir(path):
        shutil.rmtree(path)
    for name, stack in images.items():
        os.makedirs(os.path.join(path, name))
        for i, image in enumerate(stack):
            _write_pgm(os.path.join(path, name, f"{i:03d}.pgm"), image)


# ---- the nets: a file keyed by the recipe ----


def recipe_hash(config: Dict[str, Any]) -> str:
    """Names the nets by what made them: the training recipe of the
    configuration and the sources that training runs through."""
    digest = hashlib.sha256()
    digest.update(json.dumps({"nets": config["nets"],
                              "frame_size": config["frame_size"],
                              "max_faces": config["max_faces"]},
                             sort_keys=True).encode())
    for module in RECIPE_SOURCES:
        with open(importlib.util.find_spec(module).origin, "rb") as fh:
            digest.update(fh.read())
    return digest.hexdigest()[:12]


def find_nets(config: Dict[str, Any]) -> Tuple[Optional[str], str]:
    """(directory that holds the matching nets or None, the recipe hash)."""
    tag = recipe_hash(config)
    for base in (os.path.join(BENCH_DIR, "nets"),
                 os.path.join(work_dir(), "nets")):
        path = os.path.join(base, tag)
        if all(os.path.isfile(os.path.join(path, f)) for f in NET_FILES):
            return path, tag
    return None, tag


def train_nets(config: Dict[str, Any], out_dir: str,
               say: Callable[[str], None]) -> None:
    """Detector, stage-1 gate and embedder, trained with the program's own
    training code on scenes and faces this benchmark renders."""
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.apps import train as train_app
    from opencv_facerecognizer_tpu.models import detector as detector_mod
    from opencv_facerecognizer_tpu.models.cascade import FaceGate

    recipe = config["nets"]
    frame_size = tuple(config["frame_size"])
    rng = np.random.default_rng(int(recipe["seed"]))
    os.makedirs(out_dir, exist_ok=True)

    t0 = time.perf_counter()
    max_boxes = int(recipe["scene_max_faces"])
    n_scenes = int(recipe["scenes"])
    scenes = np.zeros((n_scenes, *frame_size), np.float32)
    boxes = np.zeros((n_scenes, max_boxes, 4), np.float32)
    counts = np.zeros((n_scenes,), np.int32)
    for i in range(n_scenes):
        # a third of the scenes are empty: the gate has to learn them
        n = 0 if i % 3 == 0 else int(rng.integers(1, max_boxes + 1))
        who = [int(v) for v in rng.integers(0, 1 << 20, size=n)]
        frame, bx = render.render_scene(
            frame_size, who, tuple(recipe["face_px"]), rng)
        scenes[i], counts[i] = frame, n
        boxes[i, :n] = bx
    det = detector_mod.CNNFaceDetector(max_faces=int(config["max_faces"]),
                                       **recipe["detector_kwargs"])
    det.load_params(jax.jit(det.net.init)(
        jax.random.PRNGKey(int(recipe["seed"])),
        jnp.zeros((1, *frame_size)))["params"])
    det.train(scenes, boxes, counts, steps=int(recipe["detector_steps"]),
              batch_size=16, seed=int(recipe["seed"]))
    det.save(os.path.join(out_dir, "detector.ckpt"))
    found = np.asarray(det.detect_batch(scenes[:32])[2]).sum(axis=1)
    say(f"nets: detector {recipe['detector_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s; on 32 training scenes it finds "
        f"{int(found.sum())} faces of {int(counts[:32].sum())}")

    t0 = time.perf_counter()
    gate = FaceGate(**recipe["gate_kwargs"]).train(
        scenes, boxes, counts, steps=int(recipe["gate_steps"]),
        seed=int(recipe["seed"]))
    gate.save(os.path.join(out_dir, "cascade.ckpt"))
    scores = np.asarray(gate.score_batch(scenes[:48]))
    say(f"nets: gate {recipe['gate_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s; lowest score on a face scene "
        f"{scores[counts[:48] > 0].min():.3f}, highest on an empty one "
        f"{scores[counts[:48] == 0].max():.3f}")

    t0 = time.perf_counter()
    face_size = tuple(config["face_size"])
    faces_dir = os.path.join(work_dir(), "train_faces")
    _write_subjects(faces_dir, {
        f"id_{k:03d}": render.render_enrolment(
            int(rng.integers(0, 1 << 20)), face_size,
            int(recipe["embedder_per_subject"]), rng)
        for k in range(int(recipe["embedder_subjects"]))})
    rc = train_app.main([
        faces_dir, os.path.join(out_dir, "embedder.ckpt"), "--model", "cnn",
        "--image-size", str(face_size[0]), str(face_size[1]),
        "--embed-dim", str(int(config["embed_dim"])),
        "--train-steps", str(int(recipe["embedder_steps"])), "--kfold", "0"])
    if rc != 0:
        raise RuntimeError(f"ocvf-train returned {rc}")
    say(f"nets: embedder {recipe['embedder_steps']} steps in "
        f"{time.perf_counter() - t0:.1f} s")


def ensure_nets(config: Dict[str, Any], say: Callable[[str], None]) -> Dict[str, Any]:
    path, tag = find_nets(config)
    trained = path is None
    if trained:
        path = os.path.join(work_dir(), "nets", tag)
        say(f"nets: no file for recipe {tag} under benchmark/nets or "
            f".bench_work/nets; training into {path}")
        train_nets(config, path, say)
    else:
        say(f"nets: recipe {tag} found at {os.path.relpath(path, ROOT)}")
    return {"dir": path, "hash": tag, "trained_now": trained}


# ---- the gallery: rows made on the device from the seed ----


def make_gallery_rows(seed: int, rows: int, dim: int, block_rows: int):
    """[rows, dim] bf16 unit vectors, made block by block in one jitted
    call (the f32 draw of a block is all that is ever live beside the
    result). ``reference_rows`` draws the same rows again with the same
    call once the program is gone."""
    import jax
    import jax.numpy as jnp

    if rows % block_rows:
        raise ValueError("gallery rows must be a multiple of block_rows")

    def make(key):
        def block(k):
            x = jax.random.normal(k, (block_rows, dim), jnp.float32)
            x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            return x.astype(jnp.bfloat16)

        keys = jax.random.split(key, rows // block_rows)
        return jax.lax.map(block, keys).reshape(rows, dim)

    key = jax.random.key(int(seed) % (1 << 31), impl="rbg")
    return jax.jit(make)(key)


def reference_rows(config: Dict[str, Any], seed: int):
    """The gallery rows of ``seed``, drawn again for the reference: the
    same call that filled the program's gallery, none of its memory."""
    return make_gallery_rows(seed, int(config["gallery"]["rows"]),
                             int(config["embed_dim"]),
                             int(config["gallery"]["block_rows"]))


def fill_gallery(gallery, seed: int, config: Dict[str, Any],
                 label_offset: int) -> int:
    """Replaces every row past the enrolled ones with a seeded unit row
    and marks all rows valid; returns the number of enrolled rows kept.
    The program has no bulk install that stays on the device (``add``
    normalizes on the host and uploads), so the new snapshot is installed
    the way ``ShardedGallery._install`` does, from device arrays."""
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.parallel.gallery import GalleryData

    data = gallery.data
    enrolled = int(data.size)
    rows = int(gallery.capacity)
    filler = make_gallery_rows(seed, rows, int(gallery.dim),
                               int(config["gallery"]["block_rows"]))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def splice(filler, old_emb, old_lab):
        head = jnp.arange(rows) < enrolled
        emb = jnp.where(head[:, None], old_emb.astype(filler.dtype), filler)
        lab = jnp.where(head, old_lab,
                        label_offset + jnp.arange(rows, dtype=jnp.int32))
        return emb, lab, jnp.ones((rows,), bool)

    emb, lab, val = splice(filler, data.embeddings, data.labels)
    del filler
    emb = jax.device_put(emb, gallery._emb_sharding)
    lab = jax.device_put(lab, gallery._lab_sharding)
    val = jax.device_put(val, gallery._valid_sharding)
    jax.block_until_ready((emb, lab, val))
    gallery._data = GalleryData(embeddings=emb, labels=lab, valid=val,
                                size=rows, epoch=data.epoch)
    return enrolled


# ---- the stack ----


class Stack:
    """What a run holds of the system under test."""

    def __init__(self):
        self.args = None
        self.pipeline = None
        self.gallery = None
        self.service = None
        self.connector = None
        self.metrics = None
        self.tracer = None
        self.names: List[str] = []
        self.enrolled_rows = 0
        self.label_offset = 0
        self.gallery_seed = 0
        self.nets: Dict[str, Any] = {}
        self.subjects_dir = ""
        self.enrol_images = np.zeros((0, 1, 1), np.float32)
        self.enrol_labels = np.zeros((0,), np.int64)
        self.split: Dict[str, float] = {}
        self.on_pop: Optional[Callable[[], None]] = None
        self.on_result: Optional[Callable[[Dict[str, Any]], None]] = None

    def queue_depth(self) -> int:
        return self.service.batcher.pending

    def queue_limit(self) -> int:
        return int(self.service.batcher.max_pending)

    def inject(self, message: Dict[str, Any]) -> None:
        from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC

        self.connector.inject(FRAME_TOPIC, message)

    def counters(self) -> Dict[str, float]:
        return self.metrics.counters()

    def ledger(self) -> Dict[str, Any]:
        return self.service.ledger()

    def top_rung_frames(self) -> int:
        return int(self.args.batch_size)

    def close(self) -> None:
        """Stops the service and drops every reference to program state,
        so that the device memory it held is free for the reference."""
        if self.service is not None:
            self.service.stop()
        self.service = self.pipeline = self.gallery = None
        self.connector = self.tracer = None


def recognize_argv(config: Dict[str, Any], nets_dir: str,
                   subjects_dir: str) -> List[str]:
    argv = ["--model", os.path.join(nets_dir, "embedder.ckpt"),
            "--detector", os.path.join(nets_dir, "detector.ckpt"),
            "--cascade", os.path.join(nets_dir, "cascade.ckpt"),
            "--gallery", subjects_dir]
    for flag, value in config["recognize_args"].items():
        if isinstance(value, bool):
            if value:
                argv.append(flag)
        elif isinstance(value, list):
            argv += [flag, *[str(v) for v in value]]
        else:
            argv += [flag, str(value)]
    return argv


def build(config: Dict[str, Any], traffic, seed: int,
          say: Callable[[str], None], trace: bool = False) -> Stack:
    """Everything up to, and including, the service's own warm-up."""
    import jax

    from opencv_facerecognizer_tpu.apps import recognize
    from opencv_facerecognizer_tpu.parallel import make_mesh
    from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
    from opencv_facerecognizer_tpu.runtime.recognizer import RESULT_TOPIC
    from opencv_facerecognizer_tpu.utils.metrics import Metrics
    from opencv_facerecognizer_tpu.utils.tracing import Tracer

    stack = Stack()
    t0 = time.perf_counter()
    stack.nets = ensure_nets(config, say)
    stack.split["nets"] = time.perf_counter() - t0

    # The enrolled subjects: the first identities of this seed's traffic.
    t0 = time.perf_counter()
    rng = np.random.default_rng([int(seed), 13])
    face_size = tuple(config["face_size"])
    stack.subjects_dir = os.path.join(work_dir(), "subjects")
    subjects = {
        f"subject_{k:03d}": np.floor(render.render_enrolment(
            identity, face_size, int(config["gallery"]["enrol_images"]), rng))
        for k, identity in enumerate(traffic.enrolled_identities())}
    _write_subjects(stack.subjects_dir, subjects)
    # as the files hold them (8-bit), in the order read_images walks them
    stack.enrol_images = np.concatenate(list(subjects.values()))
    stack.enrol_labels = np.repeat(np.arange(len(subjects)),
                                   int(config["gallery"]["enrol_images"]))
    stack.split["subjects"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    args = recognize.build_parser().parse_args(
        recognize_argv(config, stack.nets["dir"], stack.subjects_dir))
    stack.args = args
    mesh = make_mesh(devices=jax.devices()[:int(config["devices"])])
    stack.pipeline, stack.names = recognize._load_stack(args, mesh=mesh)
    stack.gallery = stack.pipeline.gallery
    stack.split["load_stack"] = time.perf_counter() - t0
    if int(stack.gallery.capacity) != int(config["gallery"]["rows"]):
        raise RuntimeError(f"gallery capacity {stack.gallery.capacity} is not "
                           f"the configuration's {config['gallery']['rows']}")

    t0 = time.perf_counter()
    stack.label_offset = len(stack.names) + 1000
    stack.gallery_seed = int(seed)
    stack.enrolled_rows = fill_gallery(stack.gallery, seed, config,
                                       stack.label_offset)
    recognize.train_quantizer_if_wanted(stack.gallery)
    stack.split["gallery_fill"] = time.perf_counter() - t0
    for line in stack.gallery.describe_matchers():
        say(f"stack: {line}")
    want = config["gallery"].get("matcher")
    if want and stack.gallery.matcher_name() != want:
        raise RuntimeError(f"the gallery selects {stack.gallery.matcher_name()!r}"
                           f", the configuration states {want!r}")

    class Connector(FakeConnector):
        """In-process connector that keeps no copy of what it carried
        (``FakeConnector.sent`` would hold every frame of the run)."""

        def publish(self, topic, message):
            with self._lock:
                handlers = list(self._handlers.get(topic, ()))
            for handler in handlers:
                handler(topic, message)

        inject = publish

    stack.connector = Connector()
    stack.metrics = Metrics()
    if trace:
        stack.tracer = Tracer(ring_size=1 << 17, sample=1.0 / 16, seed=0)
    stack.service = recognize.build_service(
        args, stack.pipeline, stack.names, stack.connector, stack.metrics,
        tracer=stack.tracer)
    if stack.service._cpu_fallback is not None:
        raise RuntimeError("a CPU fallback is armed")

    def on_result(_topic, message):
        if stack.on_result is not None:
            stack.on_result(message)

    stack.connector.subscribe(RESULT_TOPIC, on_result)

    batcher = stack.service.batcher
    get_batch = batcher.get_batch

    def observed_get_batch(block: bool = True):
        batch = get_batch(block)
        if batch is not None and stack.on_pop is not None:
            stack.on_pop()
        return batch

    batcher.get_batch = observed_get_batch  # observation only

    t0 = time.perf_counter()
    stack.service.start()  # compiles every rung, both stages, the enrol graph
    stack.split["warmup"] = time.perf_counter() - t0
    return stack

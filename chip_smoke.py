"""The one command that proves the serving path starts on the chip.

    python chip_smoke.py

One process, no arguments, no environment switches: it passes only on a
machine whose every JAX device is a TPU, run from a checkout of this
repository. In order it

1. gates on the device and prints what it found (platform, kind, count,
   versions, compile-cache directory, native loader);
2. settles the timing basis: 8 then 16 chained 4096^3 bf16 matmuls, each run
   ended by ``block_until_ready`` — the ratio must be ~2, i.e. the call
   really awaits the device;
3. trains the full-width detector, the stage-1 gate and (through
   ``ocvf-train``'s ``main``) the serving embedder for a few steps on seeded
   synthetic data, saves and re-loads all three;
4. serves through ``ocvf-recognize``'s own stack builder and service wiring
   (``apps.recognize._load_stack`` / ``build_service``): phase A on the
   default mesh at 16,384 rows (exact XLA matcher), phases B and C on an
   explicit one-device mesh at 65,536 rows (Pallas exact matcher) and
   262,144 rows (IVF shortlist + Pallas rerank). Each phase warms every
   ladder rung, enrols one subject over the control topic, lands batches on
   every rung, and checks the ledger, the counters, the labels, where the
   results live, which matcher ran, that the kernel lowered to a Mosaic
   custom call, and tie-aware agreement with ``match_global``;
5. prints a JSON summary of what it found and then, as the last line of its
   standard output, ``{"ok": true, "device": {"platform", "kind",
   "count"}}`` — and exits 0 — only if every phase ran and every check
   held. Nothing here turns a failure into a warning: a failed check is an
   exception, a non-zero exit and no result line.

The phases are functions that take sizes so ``tests/test_chip_smoke.py`` can
run them at toy size on the CPU mesh; the device gate is bypassed there by
the test calling the phases directly, never by a switch of this script. The
times printed are set-up facts (how long a start takes), not benchmarks.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: the serving shape ``ocvf-recognize`` is deployed in (README "Serving").
FRAME_SIZE = (256, 256)
MAX_FACES = 8
LADDER = (8, 32, 128)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# ---- 1. device gate ----


def device_gate() -> dict:
    """First JAX call of the process. Every device must be a TPU."""
    import jax

    devices = jax.devices()
    bad = [str(d) for d in devices if d.platform != "tpu"]
    if bad:
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {bad[:4]} "
            f"(platform {devices[0].platform!r}). Nothing was run.")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def describe_environment(cache_dir: str) -> None:
    from importlib import metadata

    import jax
    import jaxlib

    from opencv_facerecognizer_tpu.utils import native

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    say(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(jax.devices())}")
    say(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}, python {sys.version.split()[0]}")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({entries} entries at start)")
    say(f"native loader (g++ build of native/ocvf_loader.cpp): "
        f"available={native.available()} "
        f"base64_entry_point={native.b64_available()}")


# ---- 2. timing basis ----


def timing_basis(size: int = 4096, ratio_bounds=(1.6, 2.4)) -> dict:
    """Does ``block_until_ready`` await the device here? Time 8 and then 16
    chained ``size``^3 bf16 matmuls; twice the work must take about twice
    the time. Every later wall-clock number rests on this."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (size, size), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (size, size), jnp.float32)
         / np.sqrt(size)).astype(jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=2)
    def chain(x, w, n):
        for _ in range(n):
            x = jnp.dot(x, w, preferred_element_type=jnp.float32
                        ).astype(jnp.bfloat16)
        return x

    for n in (8, 16):
        chain(x, w, n).block_until_ready()  # compile + first run
    # Interleaved, best of ten: a clock ramp or a host hiccup must not land
    # on one chain length only.
    seconds = {8: float("inf"), 16: float("inf")}
    for _ in range(10):
        for n in (8, 16):
            t0 = time.perf_counter()
            chain(x, w, n).block_until_ready()
            seconds[n] = min(seconds[n], time.perf_counter() - t0)
    ratio = seconds[16] / seconds[8]
    tflops = 16 * 2.0 * size ** 3 / seconds[16] / 1e12
    out = {"size": size, "t8_ms": round(seconds[8] * 1e3, 3),
           "t16_ms": round(seconds[16] * 1e3, 3), "ratio": round(ratio, 3),
           "implied_tflops": round(tflops, 1),
           "device_kind": jax.devices()[0].device_kind}
    say(f"timing basis: 8 matmuls {out['t8_ms']} ms, 16 matmuls "
        f"{out['t16_ms']} ms, ratio {out['ratio']}, implied "
        f"{out['implied_tflops']} TFLOP/s bf16 on {out['device_kind']!r}")
    lo, hi = ratio_bounds
    check(lo <= ratio <= hi,
          f"timing basis: T(16)/T(8) = {ratio:.3f} outside [{lo}, {hi}] — "
          f"block_until_ready does not bound device work here, so no "
          f"wall-clock number from this machine can be trusted")
    return out


# ---- 3. train a few steps, save, re-load ----


def _write_pgm(path: str, image: np.ndarray) -> None:
    """Binary PGM: the classic face-dataset format, decoded by the native
    loader (no imaging library needed to write it)."""
    h, w = image.shape
    with open(path, "wb") as fh:
        fh.write(f"P5\n{w} {h}\n255\n".encode("ascii"))
        fh.write(np.clip(image, 0, 255).astype(np.uint8).tobytes())


def train_models(workdir: str, *, frame_size=FRAME_SIZE, det_kwargs=None,
                 det_steps: int = 150, gate_steps: int = 150,
                 embed_steps: int = 40, subjects: int = 8,
                 per_subject: int = 12, face_size=None, embed_dim=None,
                 face_range=(24, 56), seed: int = 0) -> dict:
    """Detector (``CNNFaceDetector.train``), stage-1 gate and the serving
    embedder (``apps.train.main --model cnn``) for a few steps each on
    seeded synthetic data; every model is saved and re-loaded through the
    checkpoint path the serving app reads. Returns the artifact paths."""
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.apps import train as train_app
    from opencv_facerecognizer_tpu.models import detector as detector_mod
    from opencv_facerecognizer_tpu.models.cascade import FaceGate
    from opencv_facerecognizer_tpu.models.embedder import (
        SERVING_EMBEDDER_KWARGS, SERVING_FACE_SIZE, CNNEmbedding,
    )
    from opencv_facerecognizer_tpu.utils import serialization
    from opencv_facerecognizer_tpu.utils.dataset import (
        make_synthetic_faces, make_synthetic_scenes,
    )

    face_size = tuple(face_size or SERVING_FACE_SIZE)
    embed_dim = int(embed_dim or SERVING_EMBEDDER_KWARGS["embed_dim"])
    os.makedirs(workdir, exist_ok=True)
    paths = {"detector": os.path.join(workdir, "detector.ckpt"),
             "cascade": os.path.join(workdir, "cascade.ckpt"),
             "model": os.path.join(workdir, "embedder.ckpt"),
             "gallery": os.path.join(workdir, "gallery")}

    # -- detector at its default width, max_faces slots --
    t0 = time.perf_counter()
    scenes, boxes, counts = make_synthetic_scenes(
        num_scenes=64, scene_size=frame_size, max_faces=4,
        face_size_range=face_range, seed=seed + 7)
    det = detector_mod.CNNFaceDetector(max_faces=MAX_FACES,
                                       **(det_kwargs or {}))
    det.load_params(jax.jit(det.net.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, *frame_size)))["params"])
    heat, size, offset, mask = detector_mod.gaussian_heatmap_targets(
        boxes[:16], counts[:16], frame_size, boxes.shape[1])
    targets = {"heatmap": jnp.asarray(heat), "size": jnp.asarray(size),
               "offset": jnp.asarray(offset), "mask": jnp.asarray(mask)}

    @jax.jit
    def eval_loss(params, images):
        return detector_mod.detector_loss(
            det.net.apply({"params": params}, images), targets)

    def det_loss() -> float:
        return float(eval_loss(det.params,
                               jnp.asarray(scenes[:16], jnp.float32)))

    loss0 = det_loss()
    det.train(scenes, boxes, counts, steps=det_steps, batch_size=16,
              seed=seed)
    loss1 = det_loss()
    check(np.isfinite(loss0) and np.isfinite(loss1) and loss1 < loss0,
          f"detector loss did not fall: {loss0} -> {loss1}")
    det.save(paths["detector"])
    restored = detector_mod.CNNFaceDetector.load(paths["detector"])
    b0, _, v0 = (np.asarray(a) for a in det.detect_batch(scenes[:4]))
    b1, _, v1 = (np.asarray(a) for a in restored.detect_batch(scenes[:4]))
    check(np.array_equal(v0, v1) and np.allclose(b0, b1, atol=1e-4),
          "detector checkpoint did not round-trip")
    say(f"train: detector {det_steps} steps, loss {loss0:.3f} -> "
        f"{loss1:.3f}, saved + re-loaded ({time.perf_counter() - t0:.1f} s)")

    # -- stage-1 cascade gate on the same scenes --
    t0 = time.perf_counter()
    gate = FaceGate().train(scenes, boxes, counts, steps=gate_steps,
                            seed=seed)
    gate.save(paths["cascade"])
    scores0 = np.asarray(gate.score_batch(scenes[:8]))
    scores1 = np.asarray(FaceGate.load(paths["cascade"]).score_batch(scenes[:8]))
    check(np.isfinite(scores0).all() and np.allclose(scores0, scores1,
                                                     atol=1e-4),
          "cascade gate checkpoint did not round-trip")
    say(f"train: cascade gate {gate_steps} steps, saved + re-loaded "
        f"({time.perf_counter() - t0:.1f} s)")

    # -- the serving embedder through ocvf-train's main --
    t0 = time.perf_counter()
    faces, labels, names = make_synthetic_faces(
        subjects, per_subject, face_size, seed=seed + 11, noise=8.0)
    for name in names:
        os.makedirs(os.path.join(paths["gallery"], name), exist_ok=True)
    per = {}
    for image, label in zip(faces, labels):
        name = names[int(label)]
        per[name] = per.get(name, 0) + 1
        _write_pgm(os.path.join(paths["gallery"], name,
                                f"{per[name]:03d}.pgm"), image)
    rc = train_app.main([
        paths["gallery"], paths["model"], "--model", "cnn",
        "--image-size", str(face_size[0]), str(face_size[1]),
        "--embed-dim", str(embed_dim), "--train-steps", str(embed_steps),
        "--kfold", "0"])
    check(rc == 0, f"ocvf-train main returned {rc}")
    serialization.register(CNNEmbedding)
    feature = serialization.load_model(paths["model"]).feature
    check(isinstance(feature, CNNEmbedding), "model checkpoint is not cnn")
    emb = np.asarray(feature.extract(faces[:8]))
    check(emb.shape == (8, embed_dim) and np.isfinite(emb).all()
          and np.allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-2),
          f"re-loaded embedder output is not unit-norm [8, {embed_dim}]")
    say(f"train: embedder {embed_steps} steps via ocvf-train "
        f"(stages {feature.stage_features}, {face_size[0]}x{face_size[1]} "
        f"-> {embed_dim}-d), saved + re-loaded "
        f"({time.perf_counter() - t0:.1f} s)")
    return paths


# ---- 4. serve ----


def _filler_rows(rows: int, dim: int, seed: int) -> np.ndarray:
    """Seeded clustered unit rows: a few thousand centres plus noise, so an
    IVF shortlist has structure to find (uniform random rows have none)."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(max(8, rows // 100), dim)).astype(np.float32)
    out = centres[rng.integers(0, len(centres), size=rows)]
    out = out + 0.35 * rng.normal(size=(rows, dim)).astype(np.float32)
    return out / np.linalg.norm(out, axis=-1, keepdims=True)


def _matcher_parity(gallery, query_counts, seed: int) -> dict:
    """Tie-aware top-1 agreement of the gallery's selected matcher with the
    XLA reference ``match_global`` on the same rows and queries."""
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.ops.ivf_match import tie_aware_agreement
    from opencv_facerecognizer_tpu.parallel.gallery import match_global

    rng = np.random.default_rng(seed)
    emb, _lab, val, _size = gallery.snapshot()
    valid_ids = np.nonzero(val)[0]
    reference = jax.jit(functools.partial(match_global, k=1,
                                          mesh=gallery.mesh))
    out = {}
    for qn in query_counts:
        q = emb[rng.choice(valid_ids, size=qn)]
        q = q + 0.03 * rng.normal(size=q.shape).astype(np.float32)
        q /= np.linalg.norm(q, axis=-1, keepdims=True)
        _labels, sims, idx = gallery.match(q, k=1)
        data = gallery.data
        _rl, ref_sims, ref_idx = reference(
            jnp.asarray(q), data.embeddings, data.valid, data.labels)
        out[int(qn)] = tie_aware_agreement(
            np.asarray(sims)[:, 0], np.asarray(idx)[:, 0],
            np.asarray(ref_sims)[:, 0], np.asarray(ref_idx)[:, 0])
    return out


def serve_phase(name: str, paths: dict, *, capacity: int, fill_rows: int,
                expect_matcher: str, mesh=None, frame_size=FRAME_SIZE,
                ladder=LADDER, face_range=(24, 56),
                parity_queries=(64, 256, 1024), min_agreement: float = 1.0,
                require_platform: str = "tpu", require_mosaic: bool = True,
                seed: int = 0) -> dict:
    """One full serve: build the stack and the service the way
    ``ocvf-recognize`` does, warm up, enrol over the control topic, land
    batches on every ladder rung, drain, and check everything."""
    import jax

    from opencv_facerecognizer_tpu.apps import recognize
    from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS
    from opencv_facerecognizer_tpu.runtime.connector import (
        FakeConnector, encode_frame,
    )
    from opencv_facerecognizer_tpu.runtime.recognizer import (
        CONTROL_TOPIC, FRAME_TOPIC, RESULT_TOPIC, STATUS_TOPIC,
    )
    from opencv_facerecognizer_tpu.utils import metric_names as mn
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    batch_size = max(ladder)
    args = recognize.build_parser().parse_args([
        "--model", paths["model"], "--detector", paths["detector"],
        "--gallery", paths["gallery"], "--cascade", paths["cascade"],
        "--source", "dir",
        "--frame-size", str(frame_size[0]), str(frame_size[1]),
        "--batch-size", str(batch_size),
        "--bucket-sizes", *[str(b) for b in ladder],
        "--capacity", str(capacity), "--gallery-dtype", "bf16",
        "--ingest-mode", "uint8", "--match-mode", "auto",
    ])
    t_setup = time.perf_counter()
    pipeline, names = recognize._load_stack(args, mesh=mesh)
    gallery = pipeline.gallery
    check(gallery.capacity == capacity,
          f"{name}: gallery capacity {gallery.capacity} != {capacity}")
    if fill_rows:
        # Labels past every subject index: filler can never be read as an
        # enrolled name (the next enrolment takes label len(names)).
        gallery.add(_filler_rows(fill_rows, gallery.dim, seed + 3),
                    len(names) + 1000 + np.arange(fill_rows, dtype=np.int32))
    recognize.train_quantizer_if_wanted(gallery)
    for line in gallery.describe_matchers():
        say(f"{name}: {line}")
    mesh_shape = {"dp": int(gallery.mesh.shape[DP_AXIS]),
                  "tp": int(gallery.mesh.shape[TP_AXIS])}
    shard_devices = {s.device for s in
                     gallery.data.embeddings.addressable_shards}
    check(len(shard_devices) == gallery.mesh.size,
          f"{name}: gallery shards sit on {len(shard_devices)} devices, "
          f"mesh is {mesh_shape}")
    check(gallery.matcher_name() == expect_matcher,
          f"{name}: gallery selects {gallery.matcher_name()!r}, expected "
          f"{expect_matcher!r}")

    connector = FakeConnector()
    metrics = Metrics()
    service = recognize.build_service(args, pipeline, names, connector,
                                      metrics)
    check(service._cascade_active and service.tracker is not None,
          f"{name}: cascade/tracker are not wired")
    check(service._cpu_fallback is None, f"{name}: cpu fallback is armed")
    check(service._bucket_ladder == sorted(ladder),
          f"{name}: ladder {service._bucket_ladder} != {sorted(ladder)}")

    # Observe every dispatch: rung, matcher mode, where the result lives.
    dispatches = []
    packed_step = pipeline.recognize_batch_packed

    def recording_step(frames):
        out = packed_step(frames)
        dispatches.append((int(frames.shape[0]),
                           pipeline.last_dispatch_info["mode"],
                           {d.platform for d in out.devices()}))
        return out

    pipeline.recognize_batch_packed = recording_step

    scenes, _boxes, counts = make_synthetic_scenes(
        num_scenes=96, scene_size=frame_size, max_faces=3,
        face_size_range=face_range, seed=seed + 101)
    scenes = scenes.astype(np.uint8)
    with_face = [i for i in range(len(scenes)) if counts[i] >= 1]
    encoded = {i: encode_frame(scenes[i]) for i in range(len(scenes))}
    subject_scene = next(i for i in with_face if counts[i] == 1)
    subject = "smoke_subject"
    sent = 0

    def send(scene_ids, **meta) -> list:
        nonlocal sent
        seqs = []
        for i in scene_ids:
            connector.inject(FRAME_TOPIC, {
                **encoded[i], "meta": {"seq": sent, **meta}})
            seqs.append(sent)
            sent += 1
        return seqs

    def drained() -> None:
        check(service.drain(timeout=300.0),
              f"{name}: drain timed out; ledger {service.ledger()}")

    setup_s = time.perf_counter() - t_setup
    t_warm = time.perf_counter()
    service.start()  # warmup compiles every rung, both cascade stages, enrol
    warmup_s = time.perf_counter() - t_warm
    dispatches.clear()  # keep serving dispatches only
    t_steady = time.perf_counter()
    try:
        # -- enrol one subject through the control topic --
        connector.inject(CONTROL_TOPIC, {"cmd": "enroll", "subject": subject,
                                         "count": 5})
        send([subject_scene] * 6)
        deadline = time.monotonic() + 180.0
        while not any(m.get("status") == "enrolled"
                      for m in connector.messages(STATUS_TOPIC)):
            check(time.monotonic() < deadline,
                  f"{name}: enrolment did not finish (the detector found "
                  f"no usable face?); statuses "
                  f"{[m.get('status') for m in connector.messages(STATUS_TOPIC)]}")
            time.sleep(0.02)
        drained()
        own = send([subject_scene] * 8)
        drained()
        # -- the tracker path: one coherent stream, frame by frame --
        for _ in range(12):
            own += send([subject_scene], stream="smoke-cam")
            drained()
        # -- every rung: a burst sized for it, then >= 3 full batches + a
        # partial with the producer holding to the intake bound --
        rungs = sorted(ladder)
        for attempt in range(4):
            seen = {b for b, _mode, _dev in dispatches}
            missing = [r for r in rungs if r not in seen]
            if not missing:
                break
            for rung in missing:
                below = max([r for r in rungs if r < rung], default=0)
                burst = min(rung, below + max(1, (rung - below) // 2))
                send([with_face[j % len(with_face)] for j in range(burst)])
                drained()
        stream_total = 3 * batch_size + batch_size // 3
        for j in range(stream_total):
            while service.frames_in_system() >= 1.5 * batch_size:
                time.sleep(0.001)
            send([j % len(scenes)])
        drained()
    finally:
        service.stop()
        del pipeline.recognize_batch_packed  # drop the recorder (and its cycle)
    steady_s = time.perf_counter() - t_steady

    # -- the ledger and the counters --
    ledger = service.ledger()
    counters = metrics.counters()
    completed = (ledger["completed"] + ledger["completed_empty"]
                 + ledger["completed_cached"])
    check(ledger["admitted"] == sent and completed == sent
          and not ledger["drops_by_reason"] and ledger["in_system"] == 0,
          f"{name}: sent {sent}, ledger {ledger}")
    for counter in (mn.BATCHES_FAILED, mn.DISPATCH_FAILURES,
                    mn.BATCHES_DEAD_LETTERED, mn.CPU_FALLBACKS,
                    mn.RECOMPILES_POST_WARMUP, mn.READBACK_ERRORS,
                    mn.LOOP_CRASHES, mn.CASCADE_ERRORS, mn.TRACK_ERRORS,
                    mn.DEGRADED_TRANSITIONS):
        check(not counters.get(counter),
              f"{name}: {counter} = {counters.get(counter)}")
    check(counters.get(mn.SUBJECTS_ENROLLED) == 1,
          f"{name}: subjects_enrolled = {counters.get(mn.SUBJECTS_ENROLLED)}")

    # -- results: one per frame, the subject's own frames carry its name --
    results = {m["meta"]["seq"]: m for m in connector.messages(RESULT_TOPIC)}
    check(len(results) == sent, f"{name}: {len(results)} results for {sent}")
    for seq in own:
        got = [f["name"] for f in results[seq]["faces"]]
        check(subject in got,
              f"{name}: frame {seq} of {subject!r} came back as {got}")

    # -- where it ran, on which rungs, with which matcher --
    rung_counts = {r: sum(1 for b, _m, _d in dispatches if b == r)
                   for r in sorted(ladder)}
    check(all(rung_counts.values()),
          f"{name}: not every rung was dispatched: {rung_counts}")
    platforms = set().union(*(d for _b, _m, d in dispatches))
    check(platforms == {require_platform},
          f"{name}: results live on {platforms}, expected "
          f"{require_platform!r}")
    modes = {m for _b, m, _d in dispatches}
    check(modes == {"ivf" if expect_matcher == "ivf" else "exact"},
          f"{name}: dispatch modes {modes} with matcher {expect_matcher}")
    check(gallery._pallas_enabled() == (expect_matcher != "xla"),
          f"{name}: pallas_enabled={gallery._pallas_enabled()} with "
          f"matcher {expect_matcher}")
    mosaic = {}
    for rung in sorted(ladder):
        text = pipeline.lower_packed(rung, *frame_size, np.uint8).as_text()
        mosaic[rung] = "tpu_custom_call" in text
    if expect_matcher == "xla":
        check(not any(mosaic.values()),
              f"{name}: exact XLA step holds a Mosaic call: {mosaic}")
    elif require_mosaic:
        check(all(mosaic.values()),
              f"{name}: no Mosaic custom call in the lowered step — the "
              f"kernel did not compile for the device: {mosaic}")
    parity = _matcher_parity(gallery, parity_queries, seed + 5)
    check(all(a >= min_agreement for a in parity.values()),
          f"{name}: tie-aware agreement with match_global {parity} below "
          f"{min_agreement}")

    out = {"phase": name, "mesh": mesh_shape, "matcher": expect_matcher,
           "capacity": capacity, "rows": int(gallery.size),
           "setup_s": round(setup_s, 1), "warmup_s": round(warmup_s, 1),
           "steady_s": round(steady_s, 1), "frames_sent": sent,
           "frames_completed": int(completed),
           "completed_empty": int(ledger["completed_empty"]),
           "completed_cached": int(ledger["completed_cached"]),
           "rung_batches": rung_counts, "mosaic_in_lowered_step": mosaic,
           "agreement_with_match_global": parity}
    say(f"{name}: OK {json.dumps(out)}")
    return out


# ---- 5. verdict ----


def verdict_lines(device: dict, basis: dict, phases: list,
                  total_s: float) -> tuple:
    """The two closing lines of a run in which every check held: the
    summary of what was found (set-up facts, no claim), then — last, and
    with exactly these keys, because the chip check parses it — the result."""
    summary = {"summary": "chip_smoke", "device": device,
               "timing_basis": basis, "phases": phases, "total_s": total_s,
               "claim": None}
    return (json.dumps(summary),
            json.dumps({"ok": True, "device": device}))


def main() -> int:
    t_start = time.perf_counter()
    device = device_gate()
    try:
        from opencv_facerecognizer_tpu.utils import compile_cache
    except ImportError as exc:
        raise SystemExit(
            f"chip_smoke: run from a checkout of the repository ({exc})")
    import jax

    from opencv_facerecognizer_tpu.parallel import make_mesh

    describe_environment(compile_cache.enable())
    basis = timing_basis()
    paths = train_models(os.path.join(OUT_DIR, "work"))
    one_device = make_mesh(devices=jax.devices()[:1])
    phases = [
        serve_phase("A", paths, capacity=16384, fill_rows=8192,
                    expect_matcher="xla"),
        serve_phase("B", paths, capacity=65536, fill_rows=49152,
                    expect_matcher="pallas", mesh=one_device),
        serve_phase("C", paths, capacity=262144, fill_rows=196608,
                    expect_matcher="ivf", mesh=one_device,
                    min_agreement=0.99),
    ]
    for line in verdict_lines(device, basis, phases,
                              round(time.perf_counter() - t_start, 1)):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

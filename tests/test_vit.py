"""The vision-transformer embedder: the flax net against the benchmark's
plain reference (``benchmark/configs/watchlist4m-vitb_reference.py``, float32
at highest precision, no flax), what it computes and in which precision, its
counts at the published sizes, its checkpoint through ``serialization`` and
``_load_stack``, one fused step through ``RecognitionPipeline`` against the
reference, the scopes it names inside ``ocvf_embed`` and the dispatch's
provenance. Everything on seeded random weights with no bias or norm at its
default, at CPU size: width 64, 2 blocks of 4 heads, patch 9 on 40x40 crops
= 16 tokens with 4 px unread, as 112 leaves 4."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opencv_facerecognizer_tpu.apps import recognize as recognize_app
from opencv_facerecognizer_tpu.models import iresnet, vit
from opencv_facerecognizer_tpu.models.cascade import FaceGate
from opencv_facerecognizer_tpu.models.classifier import NearestNeighbor
from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
from opencv_facerecognizer_tpu.models.embedder import normalize_faces
from opencv_facerecognizer_tpu.models.model import PredictableModel
from opencv_facerecognizer_tpu.ops.distance import CosineDistance
from opencv_facerecognizer_tpu.parallel import make_mesh
from opencv_facerecognizer_tpu.runtime import FakeConnector, RecognizerService
from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC, RESULT_TOPIC
from opencv_facerecognizer_tpu.utils import serialization
from opencv_facerecognizer_tpu.utils.dataset import (
    make_synthetic_faces, make_synthetic_scenes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FACE = (40, 40)
TOKENS = 16
#: a deviation at which q k^T is far from flat and every branch moves the
#: stream (the published 0.02 at width 64 leaves attention near uniform)
SMALL = dict(embed_dim=64, depth=2, heads=4, out_dim=32, init_std=0.1)
FRAME = (96, 96)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(REPO, "benchmark", "configs", "watchlist4m-vitb_reference.py")
    spec = importlib.util.spec_from_file_location("vitb_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def small():
    """(feature with seeded, calibrated parameters; the faces it saw)."""
    faces, _y, _names = make_synthetic_faces(8, 8, FACE, seed=41, noise=8.0)
    feature = vit.ViTEmbedding(input_size=FACE, seed=5, **SMALL)
    feature.compute(np.asarray(faces, np.float32))
    return feature, np.asarray(faces, np.float32)


def _cfg(feature):
    return {"heads": feature.heads, "patch": feature.patch,
            "layer_norm_eps": feature.layer_norm_eps,
            "head_bn_eps": feature.head_bn_eps}


def _f32(params):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)


def _leaves(params, name):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return [np.asarray(leaf) for path, leaf in flat if path[-1].key == name]


def test_no_bias_norm_or_moment_is_left_at_its_default(small):
    feature, _ = small
    net = feature._params["net"]
    assert all(np.abs(b).max() > 1e-3 for b in _leaves(net, "bias"))
    assert all(np.abs(s - 1.0).max() > 0.1 for s in _leaves(net, "scale"))
    assert all(np.abs(m).max() > 1e-3 for m in _leaves(net, "mean"))
    assert all(np.abs(v - 1.0).max() > 1e-3 for v in _leaves(net, "var"))
    # LayerNorms: two a block and the last; BatchNorms: the head's two
    assert len(_leaves(net, "scale")) == 2 * 2 + 1 + 2 and len(_leaves(net, "mean")) == 2
    assert "bias" not in net["block0"]["qkv"] and "bias" not in net["feature_fc1"]
    assert net["pos_embed"].shape == (TOKENS, 64)


@pytest.mark.parametrize("dtype,atol,floor,why", [
    # float32 operands on both sides: what is left is the order of the sums
    # and rsqrt against 1 / sqrt through 2 blocks: a few 1e-6 of a unit row
    (jnp.float32, 1e-5, 0.999999, "rounding order only"),
    # bf16 operands carry 8 bits (2^-9 relative a product) and the stream is
    # rounded to bf16 twice a block; the head's BatchNorms divide by the
    # deviation ACROSS faces, a fraction of a feature's size, and so magnify
    # what the blocks left. Read over three seeds: 0.009-0.010 a coordinate
    # and a cosine of 0.9997 at least, where fp8 reads 0.073-0.141 and
    # 0.964-0.985: the tolerance is three times the one, a third of the other
    (jnp.bfloat16, 0.03, 0.999, "bf16 operands and stream, f32 accumulation"),
])
def test_flax_net_agrees_with_the_plain_reference(small, reference, dtype, atol, floor, why):
    feature, faces = small
    x = normalize_faces(faces, FACE)
    net = feature.net.clone(dtype=dtype)
    ours = np.asarray(net.apply({"params": feature._params["net"]}, x))
    params = _f32(feature._params["net"])
    theirs = np.asarray(reference.embedder_forward(params, _cfg(feature), x))
    np.testing.assert_allclose(ours, theirs, atol=atol, err_msg=why)
    assert np.sum(ours * theirs, axis=-1).min() > floor, why
    # the control one step lower (float8_e4m3 operands in every matmul of the
    # same net) fails both
    low = np.asarray(reference.embedder_forward(params, _cfg(feature), x,
                                                reference.fp8))
    assert np.sum(low * theirs, axis=-1).min() < floor
    assert np.abs(low - theirs).max() > 2 * atol


def test_unit_rows_and_the_same_seed_gives_the_same_rows(small):
    feature, faces = small
    rows = np.asarray(feature.extract(faces))
    assert rows.shape == (len(faces), 32) and rows.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(rows, axis=-1), 1.0, atol=1e-5)
    again = vit.ViTEmbedding(input_size=FACE, seed=5, **SMALL)
    again.compute(faces)
    np.testing.assert_array_equal(np.asarray(again.extract(faces)), rows)
    other = vit.ViTEmbedding(input_size=FACE, seed=6, **SMALL)
    other.compute(faces)
    assert np.abs(np.asarray(other.extract(faces)) - rows).max() > 0.1


def test_calibration_centres_and_scales_the_head_over_the_calibration_set(small):
    """With the stored moments the last BatchNorm's input has zero mean and
    unit variance over the faces it was calibrated on (then scale and bias):
    the common component a random transformer gives every input is gone."""
    feature, faces = small
    net = feature._params["net"]
    x = normalize_faces(faces, FACE)
    f32 = feature.net.clone(dtype=jnp.float32)
    _out, state = f32.apply({"params": net}, x, capture_intermediates=(
        lambda module, _name: module.name == "feature_fc2"))
    before = np.asarray(state["intermediates"]["feature_fc2"]["__call__"][0])
    bn = net["feature_bn2"]
    # bf16 calibration against this f32 pass: a percent of a deviation
    np.testing.assert_allclose(before.mean(axis=0), bn["mean"], atol=0.05 * before.std())
    np.testing.assert_allclose(before.var(axis=0), bn["var"], rtol=0.1)
    normed = (before - np.asarray(bn["mean"])) / np.sqrt(np.asarray(bn["var"]) + 2e-5)
    assert np.abs(normed.mean(axis=0)).max() < 0.05
    np.testing.assert_allclose(normed.std(axis=0), 1.0, atol=0.05)
    rows = np.asarray(feature.extract(faces))
    sims = rows @ rows.T
    assert np.abs(sims[~np.eye(len(sims), dtype=bool)].mean()) < 0.1


def test_attention_equals_a_head_by_head_loop(small, reference):
    """One block's attention, every head on its own with nothing batched,
    against the reference's ``einsum`` and against the net's block."""
    feature, faces = small
    rng = np.random.default_rng(7)
    q, k, v = (rng.normal(size=(3, TOKENS, 4, 16)).astype(np.float32) for _ in range(3))
    got = np.asarray(reference.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    for m in range(3):
        for h in range(4):
            s = q[m, :, h] @ k[m, :, h].T / 4.0
            w = np.exp(s - s.max(axis=-1, keepdims=True))
            w /= w.sum(axis=-1, keepdims=True)
            np.testing.assert_allclose(got[m, :, h], w @ v[m, :, h], atol=1e-5)
    # the net's own block, float32, against the same loop on its qkv
    block = vit._Block(heads=4, dtype=jnp.float32)
    p = feature._params["net"]["block0"]
    x = jnp.asarray(rng.normal(size=(2, TOKENS, 64)), jnp.float32)
    ours = np.asarray(block.apply({"params": p}, x))
    y = np.asarray(reference.layer_norm(x, p["norm1"], 1e-5)) @ np.asarray(p["qkv"]["kernel"])
    qkv = y.reshape(2, TOKENS, 3, 4, 16)
    attn = np.zeros((2, TOKENS, 4, 16), np.float32)
    for m in range(2):
        for h in range(4):
            s = qkv[m, :, 0, h] @ qkv[m, :, 1, h].T / 4.0
            w = np.exp(s - s.max(axis=-1, keepdims=True))
            attn[m, :, h] = (w / w.sum(axis=-1, keepdims=True)) @ qkv[m, :, 2, h]
    x1 = np.asarray(x) + attn.reshape(2, TOKENS, 64) @ np.asarray(p["proj"]["kernel"]) \
        + np.asarray(p["proj"]["bias"])
    y = np.asarray(reference.layer_norm(jnp.asarray(x1), p["norm2"], 1e-5))
    y = np.clip(y @ np.asarray(p["fc1"]["kernel"]) + np.asarray(p["fc1"]["bias"]), 0, 6)
    want = x1 + y @ np.asarray(p["fc2"]["kernel"]) + np.asarray(p["fc2"]["bias"])
    np.testing.assert_allclose(ours, want, atol=2e-4)


def test_the_mlp_is_relu6_and_not_gelu(small):
    """Inputs that tell the three apart: at -1 GELU gives -0.159 and ReLU6
    0; at 8 ReLU and GELU give 8 and ReLU6 6. fc1 is made the identity on
    the first features of a stream that LayerNorm leaves at those values."""
    block = vit._Block(heads=1, mlp_ratio=1, dtype=jnp.float32)
    x = jnp.asarray([[[-1.0, 8.0, 0.5, 3.0]]])
    p = jax.tree_util.tree_map(jnp.zeros_like, block.init(jax.random.PRNGKey(0), x)["params"])
    # norm2 passes the stream through: scale sigma, bias mu of this token
    mu, sigma = float(x.mean()), float(x.std())
    p["norm2"] = {"scale": jnp.full((4,), sigma), "bias": jnp.full((4,), mu)}
    p["norm1"] = {"scale": jnp.ones((4,)), "bias": jnp.zeros((4,))}
    p["fc1"]["kernel"], p["fc2"]["kernel"] = jnp.eye(4), jnp.eye(4)
    out = np.asarray(block.apply({"params": p}, x))[0, 0]
    # attention and proj are zero: out = x + relu6(x)
    np.testing.assert_allclose(out, [-1.0 + 0.0, 8.0 + 6.0, 0.5 + 0.5, 3.0 + 3.0], atol=1e-4)


def test_pixels_past_the_last_whole_patch_change_nothing(small, reference):
    feature, faces = small
    x = np.asarray(normalize_faces(faces[:4], FACE))
    changed = x.copy()
    changed[:, 36:, :] = 99.0
    changed[:, :, 36:] = -99.0
    apply = lambda v: np.asarray(feature._apply(feature._params["net"], jnp.asarray(v)))  # noqa: E731
    np.testing.assert_array_equal(apply(x), apply(changed))
    inside = x.copy()
    inside[:, 35, 35] += 1.0  # the last pixel that is read
    assert np.abs(apply(x) - apply(inside)).max() > 0
    params = _f32(feature._params["net"])
    np.testing.assert_array_equal(
        np.asarray(reference.embedder_forward(params, _cfg(feature), jnp.asarray(x))),
        np.asarray(reference.embedder_forward(params, _cfg(feature), jnp.asarray(changed))))


def test_scores_and_softmax_are_float32_and_every_matmul_takes_bf16(small):
    """In the serving precision's jaxpr: every ``dot_general`` has bf16
    operands and an f32 result, and the softmax's ``exp`` runs on f32 scores
    of [N, heads, T, T]."""
    feature, faces = small
    x = normalize_faces(faces[:2], FACE)
    jaxpr = jax.make_jaxpr(lambda p, v: feature.net.apply({"params": p}, v))(
        feature._params["net"], x)

    def walk(eqns):
        for eqn in eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub.eqns)

    eqns = list(walk(jaxpr.jaxpr.eqns))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    # patch, (q, k, v, q k^T, A v, proj, fc1, fc2) x 2 blocks, the head's two
    assert len(dots) == 1 + 8 * 2 + 2
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16, jnp.bfloat16]
        assert e.outvars[0].aval.dtype == jnp.float32
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) == 2
    for e in exps:
        assert e.invars[0].aval.dtype == jnp.float32
        assert e.invars[0].aval.shape == (2, 4, TOKENS, TOKENS)
    # the stream between blocks is stored bf16, the rows leave as f32
    assert jaxpr.out_avals[0].dtype == jnp.float32


@pytest.mark.parametrize("name,width,depth,published,exact", [
    ("vit_b", 512, 24, 11.4, 11_437_170_688),
    ("vit_t", 256, 12, 1.5, None), ("vit_s", 512, 12, 5.7, None),
    ("vit_l", 768, 24, 25.3, None)])
def test_multiply_adds_meet_the_published_counts(name, width, depth, published, exact):
    """Four published GFLOPs figures (arcface_torch README, WebFace42M
    table) that one count of multiply-adds meets: they count multiply-adds."""
    macs = vit.multiply_adds(vit.ViT(embed_dim=width, depth=depth), vit.VIT_B_FACE_SIZE)
    assert abs(macs / 1e9 / published - 1) < 0.02, (name, macs)
    if exact is not None:
        assert macs == exact


def test_parameter_count_at_the_published_sizes_with_its_breakdown():
    net = vit.ViT()
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, *vit.VIT_B_FACE_SIZE)))["params"]
    assert vit.parameter_breakdown(shapes) == {
        "patch": 124_928, "positions": 73_728, "blocks": 24 * 3_150_848,
        "norm": 1_024, "head": 38_012_928}
    count = vit.parameter_count(shapes)
    assert count == 113_832_960 and round(count / 1e6, 2) == 113.83
    assert net.tokens(vit.VIT_B_FACE_SIZE) == 144
    assert shapes["feature_fc1"]["kernel"].shape == (73_728, 512)
    assert shapes["block23"]["qkv"]["kernel"].shape == (512, 1536)
    assert shapes["patch_embed"]["kernel"].shape == (243, 512)
    out = jax.eval_shape(lambda p, x: net.apply({"params": p}, x), shapes,
                         jnp.zeros((2, *vit.VIT_B_FACE_SIZE)))
    assert out.shape == (2, 512) and out.dtype == jnp.float32


# ---- checkpoints and the serving app ----


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory, small):
    """A gallery directory, a detector, a gate and the embedder's checkpoint,
    all in one directory as the reference reads them."""
    import cv2

    tmp = tmp_path_factory.mktemp("vit_artifacts")
    faces, y, names = make_synthetic_faces(3, 4, FACE, seed=43, noise=8.0)
    gallery_dir = tmp / "gallery"
    for image, label in zip(np.asarray(faces), y):
        os.makedirs(gallery_dir / names[label], exist_ok=True)
        n = len(os.listdir(gallery_dir / names[label]))
        cv2.imwrite(str(gallery_dir / names[label] / f"{n}.png"),
                    np.clip(image, 0, 255).astype(np.uint8))
    scenes, boxes, counts = make_synthetic_scenes(48, FRAME, max_faces=2, seed=47)
    det = CNNFaceDetector(features=(8, 16, 32), head_features=32, max_faces=2,
                          score_threshold=0.25)
    det.train(scenes, boxes, counts, steps=200, batch_size=16, learning_rate=2e-3)
    det.save(str(tmp / "detector.ckpt"))
    FaceGate().train(scenes, boxes, counts, steps=20).save(str(tmp / "cascade.ckpt"))
    feature, _ = small
    serialization.save_model(str(tmp / "embedder.ckpt"), PredictableModel(
        feature, NearestNeighbor(CosineDistance())))
    return {"dir": str(tmp), "gallery": str(gallery_dir), "scenes": scenes,
            "names": names}


def _argv(artifacts, *extra):
    return ["--model", os.path.join(artifacts["dir"], "embedder.ckpt"),
            "--detector", os.path.join(artifacts["dir"], "detector.ckpt"),
            "--cascade", os.path.join(artifacts["dir"], "cascade.ckpt"),
            "--gallery", artifacts["gallery"], "--source", "dir",
            "--frame-size", str(FRAME[0]), str(FRAME[1]), "--capacity", "64", *extra]


def _pipeline(artifacts):
    args = recognize_app.build_parser().parse_args(_argv(artifacts))
    return recognize_app._load_stack(args, mesh=make_mesh(devices=jax.devices()[:1]))


def test_checkpoint_round_trip_through_the_default_registry(artifacts, small):
    feature, faces = small
    assert serialization._registry()["vit_embedding"] is vit.ViTEmbedding
    model = serialization.load_model(os.path.join(artifacts["dir"], "embedder.ckpt"))
    loaded = model.feature
    assert isinstance(loaded, vit.ViTEmbedding)
    assert loaded.get_config() == feature.get_config()
    same = jax.tree_util.tree_map(lambda a, b: bool(np.array_equal(a, b)),
                                  feature._params["net"], loaded._params["net"])
    assert all(jax.tree_util.tree_leaves(same))
    np.testing.assert_array_equal(np.asarray(loaded.extract(faces)),
                                  np.asarray(feature.extract(faces)))


def test_load_stack_serves_the_checkpoint_with_no_flag_of_its_own(artifacts, capsys):
    pipeline, names = _pipeline(artifacts)
    assert type(pipeline.embed_net).__name__ == "ViT"
    assert pipeline.face_size == FACE and pipeline.gallery.dim == 32
    assert sorted(names) == sorted(artifacts["names"])
    packed = np.asarray(pipeline.recognize_batch_packed(
        artifacts["scenes"][:2].astype(np.uint8)))
    from opencv_facerecognizer_tpu.parallel.pipeline import unpack_result

    out = unpack_result(packed, 1)
    assert packed.shape == (2, 2, 8) and packed.dtype == np.int32
    assert np.isfinite(out.boxes).all() and np.isfinite(out.similarities).all()
    assert ((out.labels >= -1) & (out.labels < len(names))).all()
    info = pipeline.last_dispatch_info
    assert info["embedder"] == "vit_embedding" == vit.ViTEmbedding.name
    assert info["embed_slots"] == 2 * 2 and info["embed_tokens"] == 2 * 2 * TOKENS
    # the flag PR 32 deleted stays an argument error with this checkpoint too
    with pytest.raises(SystemExit) as refused:
        recognize_app.build_parser().parse_args(_argv(artifacts, "--fused-embedder"))
    assert refused.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def test_load_stack_asks_for_the_step_s_surface_not_for_a_class(artifacts, tmp_path):
    """A feature of a class ``_load_stack`` has never heard of is served
    when it has the net, the parameters and the input size; one that lacks
    them is refused, by what it lacks."""
    feature = serialization.load_model(
        os.path.join(artifacts["dir"], "embedder.ckpt")).feature

    @serialization.register
    class FourthEmbedding(vit.ViTEmbedding):
        name = "fourth_embedding"

    try:
        fourth = FourthEmbedding(**feature.get_config())
        fourth.set_state(feature.get_state())
        path = str(tmp_path / "fourth.ckpt")
        serialization.save_model(path, PredictableModel(
            fourth, NearestNeighbor(CosineDistance())))
        args = recognize_app.build_parser().parse_args(_argv(artifacts))
        args.model = path
        pipeline, _names = recognize_app._load_stack(
            args, mesh=make_mesh(devices=jax.devices()[:1]))
        assert type(pipeline.embed_net).__name__ == "ViT"
        # parameters missing: a feature that was never computed
        bare = str(tmp_path / "bare.ckpt")
        serialization.save_model(bare, PredictableModel(
            FourthEmbedding(**feature.get_config()), NearestNeighbor(CosineDistance())))
        args.model = bare
        with pytest.raises(SystemExit, match="embedder checkpoint"):
            recognize_app._load_stack(args)
    finally:
        serialization._registry().pop("fourth_embedding", None)
    source = open(recognize_app.__file__).read()
    assert "IResNetEmbedding" not in source and "CNNEmbedding" not in source


def test_one_pipeline_step_against_the_reference(artifacts, reference):
    """Boxes, embeddings and similarities of the fused step, held against
    the plain reference's detect -> crop -> embed -> match reading the same
    three files (embeddings and similarities, not labels: on seeded weights
    the best row changes on rounding)."""
    pipeline, _ = _pipeline(artifacts)
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(40, 32)).astype(np.float32)
    rows /= np.linalg.norm(rows, axis=-1, keepdims=True)
    pipeline.gallery.add(rows, 100 + np.arange(40, dtype=np.int32))
    frames = np.floor(artifacts["scenes"][:8]).astype(np.float32)
    result = pipeline.recognize_batch(frames)
    ref = reference.Reference(artifacts["dir"], FACE)
    boxes, _scores, valid = ref.detect(frames)
    ours_valid = np.asarray(result.valid)
    assert ours_valid.sum() >= 4, "the tiny detector found too little to compare"
    agree = ours_valid & valid
    gap = np.abs(np.asarray(result.boxes) - boxes).max(axis=-1)[agree]
    assert agree.sum() >= 0.75 * ours_valid.sum() and np.median(gap) < 0.5, gap
    # the embedder on the boxes the program served: the step's own rows are
    # not handed out, so its similarities to every stored row stand for them
    emb = ref.embed(frames, np.asarray(result.boxes)).reshape(-1, 32)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=-1), 1.0, atol=1e-5)
    data = pipeline.gallery.data
    stored = np.asarray(data.embeddings[:data.size], np.float32)
    sims = emb @ stored.T
    flat_valid = ours_valid.reshape(-1)
    served_sim = np.asarray(result.similarities).reshape(-1)[flat_valid]
    # bf16 against f32 through the net (0.08 a coordinate of the row, above:
    # a quarter of it on a similarity, the sum over 32 coordinates of a unit row)
    np.testing.assert_allclose(served_sim, sims.max(axis=1)[flat_valid], atol=0.03)
    labels = np.asarray(data.labels[:data.size])
    served_lab = np.asarray(result.labels).reshape(-1)[flat_valid]
    at_served = np.array([sims[i, labels == lab].max()
                          for i, lab in zip(np.flatnonzero(flat_valid), served_lab)])
    assert np.all(sims.max(axis=1)[flat_valid] - at_served < 0.03)


def test_the_lowered_step_holds_vit_attn_under_ocvf_embed_for_this_net_only(artifacts):
    pipeline, _ = _pipeline(artifacts)
    pipeline.recognize_batch_packed(artifacts["scenes"][:4].astype(np.uint8))
    text = pipeline.lower_packed(4, *FRAME, np.uint8).as_text(debug_info=True)
    for scope in ("vit_attn", "vit_mlp", "vit_head"):
        assert f"ocvf_embed/ViT/block0/{scope}" in text or (
            scope == "vit_head" and "ocvf_embed/ViT/vit_head" in text), scope
    # the inner scopes never stand outside ocvf_embed, and the accepted
    # reader's pattern does not match them: it files them under ocvf_embed
    import re

    from benchmark.readers import trace_scope_time

    named = re.findall(r'loc\("([^"]*/vit_(?:attn|mlp|head)/[^"]*)"', text)
    assert named and all(trace_scope_time.SCOPE.search(n).group(1) == "ocvf_embed"
                         for n in named)
    assert not trace_scope_time.SCOPE.search("vit_attn vit_mlp vit_head")
    # a convolutional embedder's step names none of them
    from scripts.chaos_soak import build_stack

    other, _mesh = build_stack(frame_shape=(32, 32), face=(16, 16))
    other.recognize_batch_packed(np.zeros((4, 32, 32), np.uint8))
    theirs = other.lower_packed(4, 32, 32, np.uint8).as_text(debug_info=True)
    assert "ocvf_embed/" in theirs and "/vit_" not in theirs
    assert other.last_dispatch_info["embedder"] == "cnn_embedding"
    assert "embed_tokens" not in other.last_dispatch_info
    assert iresnet.IResNet.feature_name == iresnet.IResNetEmbedding.name


def test_dispatch_names_the_embedder_and_embed_tokens_counts_slots_times_tokens(artifacts):
    """3 frames dispatch at the 4 rung, 7 at the 8 rung: 2 face slots a
    frame and 16 tokens a crop, whatever the frames hold."""
    from opencv_facerecognizer_tpu.utils.tracing import BATCH_TOPIC, Tracer

    pipeline, _names = _pipeline(artifacts)
    connector = FakeConnector()
    tracer = Tracer(ring_size=256, sample=1.0, seed=0)
    service = RecognizerService(pipeline, connector, batch_size=8,
                                bucket_sizes=(4, 8), frame_shape=FRAME,
                                flush_timeout=0.05, similarity_threshold=0.0,
                                tracer=tracer)
    service.start(warmup=False)
    try:
        sent = 0
        for burst in (3, 7):
            for _ in range(burst):
                connector.inject(FRAME_TOPIC, {"frame": np.zeros(FRAME, np.float32),
                                               "meta": {"i": sent}})
                sent += 1
            deadline = time.monotonic() + 120
            while (len(connector.messages(RESULT_TOPIC)) < sent
                   and time.monotonic() < deadline):
                time.sleep(0.01)
    finally:
        assert service.drain(timeout=60.0)
        service.stop()
    assert service.metrics.counter("batches_dispatched") == 2
    assert service.metrics.counter("embed_slots") == (4 + 8) * 2
    assert service.metrics.counter("embed_tokens") == (4 + 8) * 2 * TOKENS
    spans = [s for s in tracer.snapshot(BATCH_TOPIC) if s["stage"] == "dispatch"]
    assert {s.get("embedder") for s in spans} == {"vit_embedding"}
    assert {s.get("detector") for s in spans} == {"heatmap"}


# ---- the attention's two forms (``ops.vit_attention``) ----------------------

#: rows of three seeded faces through the parent's net (commit 338fec9, the
#: attention still in ``_Block``), read on the CPU when the fixture was written
PARENT_ROWS = np.array([
    [0.200822, 0.081612, 0.697326, 0.339529, 0.004741, -0.554701, -0.197516, -0.068833],
    [-0.23742, 0.073575, 0.090533, -0.663633, 0.127126, 0.405256, 0.18274, 0.525191],
    [-0.341497, -0.194927, -0.641598, -0.261807, 0.059307, 0.35726, 0.121907, 0.468166]],
    np.float32)


def test_a_checkpoint_the_parent_wrote_loads_and_gives_the_parent_s_rows():
    """``tests/fixtures/vit_written_by_338fec9.ckpt``: a ViT of width 32, 2
    blocks of 2 heads on 18x18 crops, seeded, calibrated and saved by the
    parent's code. The parameter tree is unchanged (the stored qkv kernel is
    one [d, 3 d] array under ``block<i>/qkv/kernel``), so it loads into this
    net leaf for leaf and embeds as it did."""
    path = os.path.join(REPO, "tests", "fixtures", "vit_written_by_338fec9.ckpt")
    feature = serialization.load_model(path).feature
    assert isinstance(feature, vit.ViTEmbedding) and feature.embed_dim == 32
    fresh = jax.eval_shape(feature.net.init, jax.random.PRNGKey(0),
                           jnp.zeros((1, 18, 18)))["params"]
    shapes = lambda tree: {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v
                           in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert shapes(feature._params["net"]) == shapes(fresh)
    assert shapes(fresh)["['block1']['qkv']['kernel']"] == ((32, 96), "float32")
    faces = np.random.default_rng(47).uniform(0, 255, (6, 18, 18)).astype(np.float32)
    rows = np.asarray(feature.extract(faces[:3]))
    # the same lowered text on the CPU; another machine's CPU may round one
    # bf16 activation the other way
    np.testing.assert_allclose(rows, PARENT_ROWS, atol=2e-3)


def test_the_dispatch_names_the_attention_s_form_for_this_net_only(artifacts):
    """Read from the text the step lowers to for its devices, once a number
    of slots: on the CPU XLA's form (the kernel's name stands nowhere in the
    text; ``tests/test_pallas_match.py`` finds it 24 times in the text lowered
    for a v5e); the key is absent for an embedder that states no kernel,
    whose step is lowered once as before."""
    pipeline, _ = _pipeline(artifacts)
    assert pipeline._attention_kernel == "vit_attention"
    frames = artifacts["scenes"][:4].astype(np.uint8)
    pipeline.recognize_batch_packed(frames)
    assert pipeline.last_dispatch_info["embed_attention"] == "xla"
    slots = pipeline.last_dispatch_info["embed_slots"]
    assert pipeline._attention_forms == {slots: "xla"}
    pipeline._attention_forms[slots] = "kernel"   # read once: not lowered again
    pipeline.recognize_batch_packed(frames)
    assert pipeline.last_dispatch_info["embed_attention"] == "kernel"
    from scripts.chaos_soak import build_stack

    other, _mesh = build_stack(frame_shape=(32, 32), face=(16, 16))
    other.recognize_batch_packed(np.zeros((4, 32, 32), np.uint8))
    assert "embed_attention" not in other.last_dispatch_info
    assert other._attention_forms == {}


@pytest.mark.parametrize("transform", ["grad", "vmap"])
def test_grad_and_vmap_over_the_net_at_a_shape_the_kernel_takes(transform):
    """8 crops through a net of width 128 in heads of 64: every block's
    attention is the primitive that chooses between the two forms, and
    ``jax.grad`` and ``jax.vmap`` over ``ViT.apply`` trace through it as they
    did when XLA's form was the only one."""
    from opencv_facerecognizer_tpu.ops import vit_attention

    net = vit.ViT(embed_dim=128, depth=2, heads=2, patch=9, out_dim=8)
    crops = jnp.asarray(np.random.default_rng(3).normal(size=(8, 18, 18)), jnp.float32)
    assert vit_attention.fits(8, net.tokens((18, 18)), 128, 2)
    params = net.init(jax.random.PRNGKey(0), crops)["params"]
    assert "vit_attend" in str(jax.make_jaxpr(lambda x: net.apply({"params": params}, x))(crops))
    if transform == "grad":
        grads = jax.grad(lambda p: jnp.sum(net.apply({"params": p}, crops)[:, 0]))(params)
        norm = float(jnp.linalg.norm(grads["block0"]["qkv"]["kernel"]))
        assert np.isfinite(norm) and norm > 0
    else:
        rows = jax.vmap(lambda x: net.apply({"params": params}, x))(jnp.stack([crops, -crops]))
        np.testing.assert_allclose(np.asarray(rows[0]),
                                   np.asarray(net.apply({"params": params}, crops)), atol=1e-5)


@pytest.mark.parametrize("form,counted", [("kernel", True), ("xla", False), (None, False)])
def test_embed_attn_kernel_slots_counts_the_slots_of_steps_whose_attention_was_the_kernel(
        form, counted):
    """``embed_attn_kernel_slots`` beside ``embed_slots`` in the loop's one
    counter call a batch: equal where every step's attention lowered to the
    kernel, 0 where it lowered to XLA's form or the embedder has none; the
    batch span carries the form."""
    from opencv_facerecognizer_tpu.runtime.fakes import InstantPipeline
    from opencv_facerecognizer_tpu.utils import metric_names as mn
    from opencv_facerecognizer_tpu.utils.tracing import BATCH_TOPIC, Tracer

    class Reporting(InstantPipeline):
        def recognize_batch_packed(self, frames):
            packed = super().recognize_batch_packed(frames)
            slots = int(np.asarray(frames).shape[0]) * 2
            self.last_dispatch_info.update(embed_slots=slots, detect_frames=slots // 2)
            if form is not None:
                self.last_dispatch_info["embed_attention"] = form
            return packed

    connector = FakeConnector()
    tracer = Tracer(ring_size=256, sample=1.0, seed=0)
    service = RecognizerService(Reporting((16, 16)), connector, batch_size=8,
                                bucket_sizes=(4, 8), frame_shape=(16, 16),
                                flush_timeout=0.05, tracer=tracer)
    service.start(warmup=False)
    try:
        sent = 0
        for burst in (3, 7):
            for _ in range(burst):
                connector.inject(FRAME_TOPIC, {"frame": np.zeros((16, 16), np.float32),
                                               "meta": {"i": sent}})
                sent += 1
            deadline = time.monotonic() + 60
            while (len(connector.messages(RESULT_TOPIC)) < sent
                   and time.monotonic() < deadline):
                time.sleep(0.01)
    finally:
        assert service.drain(timeout=30.0)
        service.stop()
    assert service.metrics.counter(mn.EMBED_SLOTS) == (4 + 8) * 2
    assert service.metrics.counter(mn.EMBED_ATTN_KERNEL_SLOTS) == (
        (4 + 8) * 2 if counted else 0)
    spans = [s for s in tracer.snapshot(BATCH_TOPIC) if s["stage"] == "dispatch"]
    assert len(spans) == 2 and {s.get("attention") for s in spans} == {form}


@pytest.mark.parametrize("make", [
    lambda: vit.ViTEmbedding(input_size=(18, 18), embed_dim=32, depth=1, heads=2, out_dim=8),
    lambda: iresnet.IResNetEmbedding(input_size=(16, 16), embed_dim=8, stem_features=4,
                                     stage_features=(4, 4, 8, 8), stage_blocks=(1, 1, 1, 1))],
    ids=["vit", "iresnet"])
def test_a_feature_is_freed_by_its_reference_count_alone(make):
    """The jitted ``_apply`` holds the net and not the feature: no cycle, so
    a feature that goes out of scope gives its parameters back at once (a
    set-up that makes one embedder and loads another held 455 MB of ViT-B
    twice on the chip until the cycle collector happened to run)."""
    import gc
    import weakref

    gc.collect()
    gc.disable()
    try:
        feature = make()
        size = feature.input_size
        feature.compute(np.random.default_rng(0).uniform(0, 255, (4, *size)).astype(np.float32))
        feature.extract(np.zeros((2, *size), np.float32))
        gone = weakref.ref(feature)
        del feature
        assert gone() is None
    finally:
        gc.enable()

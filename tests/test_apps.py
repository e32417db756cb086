"""CLI app tests: the reference's script surface (SURVEY.md §2.1
"Packaging/CLI") driven through main(argv)."""

import json
import os

import numpy as np
import pytest

from opencv_facerecognizer_tpu.apps import recognize as recognize_app
from opencv_facerecognizer_tpu.apps import train as train_app
from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_faces, make_synthetic_scenes


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _options(parser):
    """``ocvf-recognize``'s own options (``-h`` is argparse's)."""
    return [a for a in parser._actions
            if a.option_strings and a.dest != "help"]


#: the flags PR 32 deleted, each with an abbreviation no living flag owns
REMOVED_FLAGS = {
    "--no-readback-worker": "--no-readback",
    "--readback-poll-ms": "--readback-p",
    "--drain-poll-ms": "--drain",
    "--fused-embedder": "--fused",
    "--transfer-uint8": "--transfer",
}


@pytest.mark.parametrize("flag", list(REMOVED_FLAGS))
def test_removed_flags_are_refused(flag, capsys):
    """The five flags PR 32 deleted are argument errors, abbreviated too:
    argparse's prefix matching must not land one on a neighbour
    (``--readback-deadline``, ``--no-cascade``, ``--no-track-cache``)."""
    required = ["--model", "m", "--detector", "d", "--gallery", "g"]
    for spelling in (flag, REMOVED_FLAGS[flag]):
        argv = required + [spelling] + (["5"] if flag.endswith("-ms") else [])
        with pytest.raises(SystemExit) as refused:
            recognize_app.build_parser().parse_args(argv)
        assert refused.value.code == 2, spelling
        assert "unrecognized arguments" in capsys.readouterr().err, spelling


@pytest.mark.parametrize("what, pin", [
    ("parser", 76), ("service", 30), ("pipeline", 8)],
    ids=["parser", "service", "pipeline"])
def test_option_census(what, pin):
    """ROADMAP Design 7, as a test: how many values a user can set."""
    import inspect

    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.runtime.recognizer import RecognizerService

    if what == "parser":
        count = len(_options(recognize_app.build_parser()))
    else:
        init = (RecognizerService if what == "service"
                else RecognitionPipeline).__init__
        count = len(inspect.signature(init).parameters) - 1  # self
    assert count <= pin, (
        f"{what}: {count} options, pinned at {pin}: lower the pin when you "
        "delete one; raising it needs two callers that exist, see "
        "simplicity-review Options")


def _is_default(default, stated):
    """A parser's or constructor's default against the value a
    configuration file writes for it ("none", a number, a choice)."""
    if stated == "none" or default is None:
        return stated == "none" and default is None
    try:
        return float(default) == float(stated)
    except (TypeError, ValueError):
        return str(default) == stated


@pytest.mark.parametrize("name", ["watchlist8m", "watchlist4m-r50",
                                  "watchlist48m-tp4"])
def test_benchmark_configurations_parse(name):
    """What a benchmark configuration says of ``ocvf-recognize`` holds for
    the parser in the tree: today only a run on the chip finds a
    configuration that names a deleted flag or a default that moved."""
    import inspect
    import re

    from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher
    from opencv_facerecognizer_tpu.runtime.recognizer import RecognizerService

    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as fh:
        config = json.load(fh)
    parser = recognize_app.build_parser()
    by_flag = {flag: a for a in _options(parser) for flag in a.option_strings}

    # the way benchmark/stacks/recognize.py::recognize_argv flattens them
    argv = ["--model", "m", "--detector", "d", "--gallery", "g"]
    for flag, value in config["recognize_args"].items():
        assert not isinstance(value, bool), flag
        argv += [flag, *[str(v) for v in (
            value if isinstance(value, list) else [value])]]
    args = parser.parse_args(argv)
    for flag, value in config["recognize_args"].items():
        assert flag in by_flag, flag
        got = getattr(args, by_flag[flag].dest)
        assert (list(got) if isinstance(value, list) else got) == value, flag

    for key in config["departures_from_ocvf_recognize_defaults"]:
        flag = key.split()[0]
        assert flag in by_flag, key
        stated = re.search(r"\(default ([^,)]+)", key)
        if stated:
            assert _is_default(by_flag[flag].default, stated.group(1)), key

    # "flush-ms 30, target-latency-ms none, max_pending 256 (...), ...":
    # a hyphenated name is a flag, an underscored one a constructor
    # parameter of the service or its batcher; the rest is prose
    params = {**inspect.signature(FrameBatcher.__init__).parameters,
              **inspect.signature(RecognizerService.__init__).parameters}
    checked = 0
    for item in config["kept_at_default"].split(". ")[0].split(", "):
        word, value = item.split()[:2]
        if "-" in word:
            assert "--" + word in by_flag, item
            assert _is_default(by_flag["--" + word].default, value), item
        elif "_" in word:
            assert word in params, item
            assert _is_default(params[word].default, value), item
        else:
            continue
        checked += 1
    assert checked >= 6

    # The committed nets are named by a hash over the training recipe AND
    # the bytes of the program's training sources (models/embedder.py
    # among them): an edit there orphans them, and every run then trains
    # its nets in set-up (57 s on the chip; PERF.md section 5).
    from benchmark.stacks import recognize as stack

    recipe = config["nets"].get("gate_and_detector", config["nets"])
    found, tag = stack.find_nets({**config, "nets": recipe})
    assert found is not None and found.startswith(
        os.path.join(REPO, "benchmark", "nets")), (
        f"no committed nets under benchmark/nets/{tag}: one of "
        f"{stack.RECIPE_SOURCES} changed")


def _write_dataset(root, images, labels, names):
    import cv2

    for name in names:
        os.makedirs(os.path.join(root, name), exist_ok=True)
    counters = {}
    for img, label in zip(images, labels):
        subject = names[label]
        i = counters.get(subject, 0)
        counters[subject] = i + 1
        cv2.imwrite(os.path.join(root, subject, f"{i}.png"), img.astype(np.uint8))


def test_train_app_classic(tmp_path, capsys):
    X, y, names = make_synthetic_faces(4, 6, (32, 32), seed=51)
    data_dir = str(tmp_path / "data")
    _write_dataset(data_dir, X, y, names)
    model_path = str(tmp_path / "model.ckpt")
    plot_path = str(tmp_path / "eigen.png")
    rc = train_app.main([
        data_dir, model_path, "--model", "fisherfaces", "--image-size", "32", "32",
        "--kfold", "2", "--eigenfaces-plot", plot_path,
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "mean k-fold accuracy" in out
    assert os.path.exists(model_path)
    assert os.path.exists(plot_path)

    from opencv_facerecognizer_tpu.utils import serialization

    model = serialization.load_model(model_path)
    assert model.subject_names == names


def test_train_app_rejects_bad_dataset(tmp_path):
    with pytest.raises((ValueError, FileNotFoundError)):
        train_app.main([str(tmp_path / "nope"), str(tmp_path / "m.ckpt")])


@pytest.fixture(scope="module")
def app_artifacts(tmp_path_factory):
    """Trained CNN model + detector checkpoints, gallery dir, frames dir —
    shared by the recognize-app tests (training them is the slow part)."""
    import cv2

    tmp_path = tmp_path_factory.mktemp("app_artifacts")
    X, y, names = make_synthetic_faces(3, 6, (32, 32), seed=53, noise=8.0)
    data_dir = str(tmp_path / "gallery")
    _write_dataset(data_dir, X, y, names)
    model_path = str(tmp_path / "cnn.ckpt")
    rc = train_app.main([
        data_dir, model_path, "--model", "cnn", "--image-size", "32", "32",
        "--kfold", "0", "--embed-dim", "32", "--train-steps", "30",
    ])
    assert rc == 0

    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector

    scenes, boxes, counts = make_synthetic_scenes(32, (96, 96), max_faces=2, seed=55)
    det = CNNFaceDetector(features=(8, 16, 32), head_features=32, max_faces=4,
                          score_threshold=0.25)
    det.train(scenes, boxes, counts, steps=150, batch_size=16, learning_rate=2e-3)
    det_path = str(tmp_path / "det.ckpt")
    det.save(det_path)

    frames_dir = str(tmp_path / "frames")
    os.makedirs(frames_dir)
    test_scenes, _, test_counts = make_synthetic_scenes(4, (96, 96), max_faces=2, seed=57)
    for i, scene in enumerate(test_scenes):
        cv2.imwrite(os.path.join(frames_dir, f"f{i}.png"), scene.astype(np.uint8))

    return {
        "data_dir": data_dir, "model_path": model_path, "det_path": det_path,
        "frames_dir": frames_dir, "names": names, "test_scenes": test_scenes,
        "tmp_path": tmp_path,
    }


@pytest.mark.slow
def test_recognize_app_dir_mode(app_artifacts, capsys):
    a = app_artifacts
    profile_dir = str(a["tmp_path"] / "trace")
    rc = recognize_app.main([
        "--model", a["model_path"], "--detector", a["det_path"],
        "--gallery", a["data_dir"],
        "--source", "dir", "--dir", a["frames_dir"], "--frame-size", "96", "96",
        "--batch-size", "4", "--similarity-threshold", "0.0",
        "--profile-dir", profile_dir, "--profile-batches", "1",
    ])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert len(lines) == 4
    results = [json.loads(l) for l in lines]
    files = sorted(r["meta"]["file"] for r in results)
    assert files == [f"f{i}.png" for i in range(4)]
    for r in results:
        for face in r["faces"]:
            assert face["name"] in a["names"] or face["name"] == "unknown"
    # --profile-dir produced a loadable trace (SURVEY.md §5.1)
    trace_files = [
        os.path.join(root, f)
        for root, _dirs, fs in os.walk(profile_dir) for f in fs
    ]
    assert trace_files, "profiler trace directory is empty"


@pytest.mark.slow
def test_recognize_app_jsonl_stdin_eof_terminates(app_artifacts, monkeypatch, capsys):
    """Regression: jsonl mode used to spin `while True` forever after stdin
    EOF; it must now shut down cleanly on its own."""
    import io
    import sys
    import threading

    from opencv_facerecognizer_tpu.runtime.connector import encode_frame
    from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC

    a = app_artifacts
    n_frames = 5
    lines = [
        json.dumps({"topic": FRAME_TOPIC,
                    "data": {**encode_frame(a["test_scenes"][i % 4].astype(np.float32)),
                             "meta": {"seq": i}}})
        for i in range(n_frames)
    ]
    # Final line deliberately lacks the trailing newline: still a message.
    stdin_text = "\n".join(lines + [
        json.dumps({"topic": "ocvfacerec/control", "data": {"cmd": "stats"}})
    ])
    monkeypatch.setattr(sys, "stdin", io.StringIO(stdin_text))

    rc_box = {}

    def run():
        rc_box["rc"] = recognize_app.main([
            "--model", a["model_path"], "--detector", a["det_path"],
            "--gallery", a["data_dir"], "--source", "jsonl",
            "--frame-size", "96", "96", "--batch-size", "2",
            "--similarity-threshold", "0.0",
        ])

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=300)
    assert not t.is_alive(), "jsonl mode did not terminate on stdin EOF"
    assert rc_box["rc"] == 0
    # EOF shutdown must DRAIN, not drop: every piped frame gets a result.
    out_lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    results = [json.loads(l) for l in out_lines
               if json.loads(l).get("topic") == "ocvfacerec/results"]
    seqs = sorted(r["data"]["meta"]["seq"] for r in results)
    assert seqs == list(range(n_frames)), seqs


def test_detector_checkpoint_roundtrip(tmp_path):
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector

    scenes, boxes, counts = make_synthetic_scenes(8, (64, 64), max_faces=1, seed=59)
    det = CNNFaceDetector(features=(8, 8, 16), head_features=16, max_faces=2)
    det.train(scenes, boxes, counts, steps=10, batch_size=8)
    path = str(tmp_path / "det.ckpt")
    det.save(path)
    restored = CNNFaceDetector.load(path)
    assert restored.max_faces == 2
    b1, s1, v1 = (np.asarray(v) for v in det.detect_batch(scenes[:2]))
    b2, s2, v2 = (np.asarray(v) for v in restored.detect_batch(scenes[:2]))
    np.testing.assert_allclose(b1, b2, atol=1e-5)
    np.testing.assert_array_equal(v1, v2)


def test_detector_save_before_train_raises(tmp_path):
    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector

    with pytest.raises(RuntimeError):
        CNNFaceDetector().save(str(tmp_path / "x.ckpt"))


@pytest.mark.slow
def test_recognize_app_pp_mode(app_artifacts, capsys):
    """--parallel pp serves through the two-stage pipeline executor; on the
    8-virtual-device CPU mesh the devices split 4|4."""
    a = app_artifacts
    rc = recognize_app.main([
        "--model", a["model_path"], "--detector", a["det_path"],
        "--gallery", a["data_dir"],
        "--source", "dir", "--dir", a["frames_dir"], "--frame-size", "96", "96",
        "--batch-size", "4", "--similarity-threshold", "0.0",
        "--parallel", "pp",
    ])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert len(lines) == 4
    results = [json.loads(l) for l in lines]
    assert any(r["faces"] for r in results)
    for r in results:
        for face in r["faces"]:
            assert face["name"] in a["names"] or face["name"] == "unknown"

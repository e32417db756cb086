"""``chip_smoke.py`` on the CPU: the script itself must refuse to run (the
device gate has no bypass), and its phase functions — called directly, at toy
size, on the 8-virtual-device CPU mesh with the kernels in interpret mode —
must pass, so the chip run is never the first time the command executes.

Also home to the ``ocvf-recognize`` exit-code contract: it shares the toy
checkpoints trained here (training them is the slow part)."""

import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from opencv_facerecognizer_tpu.apps import recognize  # noqa: E402
from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh  # noqa: E402

TOY = dict(frame_size=(96, 96), face_range=(20, 36))
SERVE = dict(ladder=(2, 8), parity_queries=(16, 32),
             require_platform="cpu", require_mosaic=False, **TOY)


def test_script_refuses_to_run_without_a_tpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero at the device
    gate, before anything is trained, and no verdict line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "train:" not in proc.stdout and '"ok"' not in proc.stdout


def test_timing_basis_runs_and_checks_its_ratio():
    out = chip_smoke.timing_basis(size=64, ratio_bounds=(0.0, float("inf")))
    assert out["t8_ms"] > 0 and out["t16_ms"] > 0
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.timing_basis(size=64, ratio_bounds=(1e6, 2e6))


def test_last_line_is_exactly_the_result_object():
    """The chip check parses the last line of standard output: one JSON
    object with the keys ``ok`` and ``device`` (``platform``, ``kind``,
    ``count``) and no other. What the run found goes on the line before."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary, last = chip_smoke.verdict_lines(device, {"ratio": 1.9},
                                             [{"phase": "A"}], 1.0)
    assert "\n" not in summary and "\n" not in last
    assert json.loads(last) == {"ok": True, "device": device}
    assert summary.endswith('"claim": null}')
    assert json.loads(summary)["phases"] == [{"phase": "A"}]


@pytest.fixture(scope="module")
def toy_paths(tmp_path_factory):
    return chip_smoke.train_models(
        str(tmp_path_factory.mktemp("chip_smoke")),
        det_kwargs=dict(features=(8, 16, 32), head_features=32),
        det_steps=120, gate_steps=60, embed_steps=4, subjects=3,
        per_subject=6, face_size=(32, 32), embed_dim=32, **TOY)


def test_phase_a_default_mesh(toy_paths):
    """Phase A as ``ocvf-recognize`` starts: every visible device on tp."""
    out = chip_smoke.serve_phase("A", toy_paths, capacity=512, fill_rows=128,
                                 expect_matcher="xla", **SERVE)
    assert out["mesh"] == {"dp": 1, "tp": len(jax.devices())}
    assert out["frames_completed"] == out["frames_sent"]
    assert all(out["rung_batches"].values())


def test_phase_c_one_device_ivf_kernel(toy_paths, monkeypatch):
    """Phase C (IVF shortlist + Pallas rerank) on the explicit one-device
    mesh, kernel interpreted; phase B differs only in which matcher the
    capacity selects, and the same kernel is exercised here by the rerank.
    The capacity thresholds and the platform test of the selection are
    patched HERE — the script has no such switch."""
    monkeypatch.setattr(
        ShardedGallery, "_pallas_enabled",
        lambda self, capacity=None: self.mesh.size == 1 and (
            self.capacity if capacity is None else capacity) >= 1024)
    monkeypatch.setattr(ShardedGallery, "IVF_MIN_CAPACITY", 4096)
    one_device = make_mesh(devices=jax.devices()[:1])
    out = chip_smoke.serve_phase("C", toy_paths, capacity=4096,
                                 fill_rows=3000, expect_matcher="ivf",
                                 mesh=one_device, min_agreement=0.99,
                                 **SERVE)
    assert out["mesh"] == {"dp": 1, "tp": 1}
    assert min(out["agreement_with_match_global"].values()) >= 0.99


def test_phase_fails_loudly_on_a_wrong_matcher(toy_paths):
    """A phase whose expectation does not hold raises — nothing in the
    smoke turns a failed check into a warning."""
    with pytest.raises(chip_smoke.SmokeFailure, match="selects 'xla'"):
        chip_smoke.serve_phase("B", toy_paths, capacity=512, fill_rows=0,
                               expect_matcher="pallas", **SERVE)


def test_recognize_dir_mode_exit_code(toy_paths, tmp_path, monkeypatch,
                                      capsys):
    """A finite source that ends with every admitted frame completed
    returns 0; one abandoned batch (a scripted dispatch fault with no
    retries left) returns non-zero and names the counter."""
    from opencv_facerecognizer_tpu.runtime.faults import FaultInjector
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    frames_dir = tmp_path / "frames"
    frames_dir.mkdir()
    scenes, _boxes, _counts = make_synthetic_scenes(
        4, TOY["frame_size"], max_faces=2, face_size_range=TOY["face_range"],
        seed=5)
    for i, scene in enumerate(scenes):
        chip_smoke._write_pgm(str(frames_dir / f"f{i}.pgm"), scene)
    argv = ["--model", toy_paths["model"], "--detector", toy_paths["detector"],
            "--gallery", toy_paths["gallery"], "--source", "dir",
            "--dir", str(frames_dir), "--frame-size", "96", "96",
            "--batch-size", "4", "--dispatch-retries", "0"]

    assert recognize.main(argv) == 0
    captured = capsys.readouterr()
    assert len([ln for ln in captured.out.splitlines()
                if ln.startswith("{")]) == 4
    # start-up log: mesh, device kind, matcher per tier, what an 8-device
    # mesh selects (the kernel on every shard from 65,536 rows a shard on
    # TPU; IVF off) and why the kernel is off here (a CPU)
    assert "mesh dp=1 tp=8" in captured.err
    assert "matcher by capacity tier" in captured.err
    assert "8 shard(s) of 512 rows" in captured.err
    assert "the IVF matcher is OFF" in captured.err
    assert "platform is cpu, not tpu" in captured.err

    build = recognize.build_service

    def faulty_build(*args, **kwargs):
        service = build(*args, **kwargs)
        service._faults = FaultInjector()
        service._faults.script("dispatch", "unavailable")
        return service

    monkeypatch.setattr(recognize, "build_service", faulty_build)
    assert recognize.main(argv) == 1
    err = capsys.readouterr().err
    assert "FAILED" in err and "batches_failed" in err
    assert "frames_failed" in err

"""The ``watchlist4m-scrfd-r50`` configuration's own files: the SCRFD cost
function and the reader of the detector's share of the peak on a small
recorded list, and a rehearsal of its stack and its reference at CPU size (a
small SCRFD and a gate trained on 64x96 scenes, a small IResNet made from
the seed): the cell runs and is correct, and with the timed path broken
underneath it is not. Rehearsal only: no device metric is read from these."""

import json
import os
import shutil

import pytest

from benchmark import peaks
from benchmark.readers import scrfd_cost, scrfd_mfu, trace_scope_time
from benchmark.tests import rehearse
from benchmark.tests.test_rehearsal_cell import BROKEN_DETECT, BROKEN_STEP

MS = 1_000_000
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "watchlist4m-scrfd-r50.crowd-vga"

#: one chip, two runs of a 128-frame step (150 ms: detect 40 in two
#: operations, decode 2, crop 3, embed 80, match 25) and one of a shorter
#: program, idle between them
STEP = "jit_packed_step(7)"
RECORDED = {"devices": {"/device:TPU:0": {
    "XLA Modules": [(STEP, 0, 150 * MS), (STEP, 200 * MS, 150 * MS),
                    ("jit_packed_step(9)", 400 * MS, 40 * MS)],
    "XLA Ops": [],
}}, "file": "unused"}
SCOPED = {"/device:TPU:0": {
    "ocvf_detect": [iv for s in (0, 200 * MS) for iv in (
        (s, s + 25 * MS), (s + 25 * MS, s + 40 * MS))] + [(400 * MS, 410 * MS)],
    "ocvf_decode": [(s + 40 * MS, s + 42 * MS) for s in (0, 200 * MS)],
    "ocvf_crop": [(s + 42 * MS, s + 45 * MS) for s in (0, 200 * MS)],
    "ocvf_embed": [(s + 45 * MS, s + 125 * MS) for s in (0, 200 * MS)],
    "ocvf_match": [(s + 125 * MS, s + 150 * MS) for s in (0, 200 * MS)],
}}
S10G = {"input_size": [480, 640], "in_channels": 3, "stem_features": [28, 28, 56],
        "stage_features": [56, 88, 88, 224], "stage_blocks": [3, 4, 2, 3],
        "neck_features": 56, "head_features": 80, "head_convs": 3, "num_anchors": 2}


def _ctx(**over):
    ctx = {"trace": RECORDED, "scoped_ops": SCOPED, "trace_lo": 0, "trace_hi": 450 * MS,
           "peaks": peaks.DEVICE_PEAKS["TPU v5 lite"], "config": {"detector": S10G},
           "counters": {"detect_frames": 2 * 128 + 32.0}}
    ctx.update(over)
    return ctx


def test_scrfd_cost_is_9_91_g_at_the_published_sizes():
    assert scrfd_cost.multiply_adds(S10G) == 9_914_793_600
    with open(os.path.join(BENCH, "configs", "watchlist4m-scrfd-r50.json")) as fh:
        config = json.load(fh)
    spec = config["detector"]
    assert scrfd_cost.multiply_adds(spec) == spec["multiply_adds_per_frame"] == 9_914_793_600
    # within 5 % of the published 9.98 G (multiply-adds) and 3.86 M
    assert abs(spec["multiply_adds_per_frame"] / 1e9 / spec["published_gflops_at_vga"] - 1) < 0.01
    assert abs(spec["parameters"] / 1e6 / spec["published_parameters_m"] - 1) < 0.01
    assert config["reduced"] == [] and config["frame_size"] == spec["input_size"]
    # the cost follows the frame and the depth
    assert scrfd_cost.multiply_adds(dict(S10G, input_size=[960, 1280])) == 4 * 9_914_793_600
    assert scrfd_cost.multiply_adds(dict(S10G, stage_blocks=[1, 1, 1, 1])) < 0.6 * 9_914_793_600


def test_detect_and_decode_times_are_read_as_sibling_scopes():
    detect = {"scope": "ocvf_detect", "module": "packed_step"}
    ctx = _ctx()
    assert trace_scope_time.read(detect, ctx) == pytest.approx(40.0)
    assert trace_scope_time.read({**detect, "scope": "ocvf_decode"}, ctx) == pytest.approx(2.0)
    # a program whose detector names no decode scope (the heat-map one, the parent)
    no_decode = {"/device:TPU:0": {k: v for k, v in SCOPED["/device:TPU:0"].items()
                                   if k != "ocvf_decode"}}
    assert trace_scope_time.read({**detect, "scope": "ocvf_decode"},
                                 _ctx(scoped_ops=no_decode)) is None


def test_scrfd_mfu_counts_the_frames_the_program_counted():
    params = {"scope": "ocvf_detect", "frames": "detect_frames", "net": "detector"}
    ctx = _ctx()
    want = 100 * 2 * 9_914_793_600 * 288 / (197e12 * 0.090)  # 40 + 40 + 10 ms
    assert scrfd_mfu.read(params, ctx) == pytest.approx(want)
    assert ctx["notes"]["scrfd_mfu"]["device_s"] == pytest.approx(0.090)
    assert scrfd_mfu.read(params, _ctx(counters={})) is None  # the parent: no such counter
    assert scrfd_mfu.read(params, _ctx(config={})) is None    # a configuration without the entry
    assert scrfd_mfu.read(params, _ctx(scoped_ops={"/device:TPU:0": {}})) is None
    assert scrfd_mfu.read(params, {"trace": None, "counters": {}}) is None


def test_the_new_files_name_their_readers_and_the_cell():
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert "workloads" not in metrics["detect_device_ms.backlog"]
    assert metrics["decode_device_ms.backlog"]["workloads"] == [CELL]
    assert metrics["detect_mfu.backlog"]["workloads"] == [CELL]
    for name, reader in (("detect_device_ms.backlog", "trace_scope_time"),
                         ("decode_device_ms.backlog", "trace_scope_time"),
                         ("detect_mfu.backlog", "scrfd_mfu")):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as fh:
            assert json.load(fh)["reader"] == reader
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "watchlist4m-scrfd-r50", "crowd-vga", 1)
    with open(os.path.join(BENCH, "traffic", "crowd.json")) as fh:
        crowd = json.load(fh)
    with open(os.path.join(BENCH, "traffic", "crowd-vga.json")) as fh:
        vga = json.load(fh)
    assert vga["face_px"] == [20, 192]
    assert {k: v for k, v in vga.items() if k not in ("face_px", "what")} == \
        {k: v for k, v in crowd.items() if k not in ("face_px", "what")}


# ---- the rehearsal ----

ARGV = ["--workload", "tiny-scrfd.trickle", "--seed", "2999000044", "--seconds", "2",
        "--trace", "0"]

#: the net served is not the net of the checkpoint: every scale of a level's
#: distances the step is handed is 25 % up (the reference reads the file's)
BROKEN_SCALE = '''
import jax as _jax
from opencv_facerecognizer_tpu.models import scrfd as _scrfd
_load = _scrfd.SCRFDDetector.load_params
def _scaled(self, params):
    _load(self, _jax.tree_util.tree_map_with_path(
        lambda p, v: v * 1.25 if str(p[-1].key).startswith("head_scale") else v, params))
_scrfd.SCRFDDetector.load_params = _scaled
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = rehearse.make_copy(str(tmp_path_factory.mktemp("bench_scrfd")))
    for src, dst in (("tiny-scrfd.json", "configs/tiny-scrfd.json"),
                     ("tiny-scrfd.limits.json", "configs/tiny-scrfd.limits.json")):
        target = os.path.join(root, "benchmark", dst)
        assert not os.path.exists(target)
        shutil.copy(os.path.join(rehearse.FIXTURES, src), target)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tiny-scrfd", "source": "rehearsal",
                             "file": "benchmark/configs/tiny-scrfd.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-scrfd.trickle", "config": "tiny-scrfd",
                               "traffic": "trickle", "chips": 1, "why": "rehearsal"})
    for metric in bench["per_layer"]:
        if metric["name"] != "settled_share.rehearsal":
            metric["workloads"].append("tiny-scrfd.trickle")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def test_the_cell_runs_from_a_trained_scrfd_and_is_correct(copy):
    rc, result, err = rehearse.run_cell(copy, ARGV)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0, json.dumps(result["compared"])
    assert result["attempted"] > 0 and result["device"]["platform"] == "cpu"
    assert "SCRFD detector 500 steps" in err and "IResNet embedder drawn from seed 5" in err
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny-scrfd.trickle.seed2999000044.trace0.json")) as fh:
        detail = json.load(fh)
    assert detail["nets"]["detector_and_gate"]["trained_now"] is True
    # 8-frame rung, 2 face slots: every dispatched step counts 8 frames, 16 slots
    window = detail["counters_window"]
    assert window["detect_frames"] == 8 * window["batches_dispatched"] > 0
    assert window["embed_slots"] == 2 * window["detect_frames"]
    assert detail["judged"]["faces_compared"] > 0
    # the second run finds the nets the first one trained
    rc, result, err = rehearse.run_cell(copy, ARGV[:3] + ["2999000045"] + ARGV[4:])
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, json.dumps(result["compared"])
    assert "detector and gate of recipe" in err and "SCRFD detector 500 steps" not in err


@pytest.mark.parametrize("patch,failing", [(BROKEN_STEP, "sim_err"),
                                           (BROKEN_DETECT, "det_miss"),
                                           (BROKEN_SCALE, "box_gap_px")])
def test_broken_timed_path_is_not_correct(copy, patch, failing):
    rc, result, err = rehearse.run_cell(copy, ARGV, patch=patch)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    value, limit = result["compared"][failing]
    assert value > limit


def test_traced_rehearsal_leaves_out_what_a_cpu_trace_cannot_name(copy):
    """A CPU's operations carry no scope: the three new readers find
    nothing, say nothing and raise nothing, and the line has the rest."""
    rc, result, err = rehearse.run_cell(copy, ARGV[:-1] + ["1"],
                                        patch=rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    metrics = result["metrics"]
    for name in ("detect_device_ms.backlog", "decode_device_ms.backlog",
                 "detect_mfu.backlog"):
        assert name not in metrics
    assert "batch_fill_share.backlog" in metrics and "dispatch_p50_ms.backlog" in metrics

"""The ``watchlist4m-vitb`` configuration's own files: the vision
transformer's cost function, the two new readers on a small recorded list
(the scope inside ``ocvf_embed`` is read, ``embed_device_ms`` goes on
holding it, a program without the scope gives nothing), and a rehearsal of
its stack and its reference at CPU size (a small ViT made from the seed,
gate and detector trained as the ``tiny`` rehearsal's): the cell runs and is
correct, and with the step, a slot or one block's ``proj`` broken
underneath it is not. Rehearsal only: no device metric is read from these."""

import json
import os
import shutil

import pytest

from benchmark import peaks
from benchmark.readers import (trace_inner_scope_time, trace_scope_time, vit_cost,
                               vit_mfu)
from benchmark.tests import rehearse
from benchmark.tests.test_r50_cell import _xplane
from benchmark.tests.test_rehearsal_cell import BROKEN_STEP

MS = 1_000_000
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VIT_B = {"input_size": [112, 112], "in_channels": 3, "patch": 9, "embed_dim": 512,
         "depth": 24, "heads": 8, "mlp_ratio": 4, "out_dim": 512}


def test_vit_cost_is_11_44_g_at_the_published_sizes_and_follows_depth_and_tokens():
    assert vit_cost.tokens(VIT_B) == 144
    assert vit_cost.multiply_adds(VIT_B) == 11_437_170_688
    with open(os.path.join(BENCH, "configs", "watchlist4m-vitb.json")) as fh:
        config = json.load(fh)
    entry = config["embedder"]
    assert vit_cost.multiply_adds(entry) == entry["multiply_adds_per_face"] == 11_437_170_688
    assert abs(entry["multiply_adds_per_face"] / 1e9 / entry["published_gflops"] - 1) < 0.01
    assert entry["tokens"] == vit_cost.tokens(entry) and config["reduced"] == []
    # the published family by the same count: ViT-T, -S, -L at 1.5, 5.7, 25.3 G
    for width, depth, published in ((256, 12, 1.5), (512, 12, 5.7), (768, 24, 25.3)):
        macs = vit_cost.multiply_adds(dict(VIT_B, embed_dim=width, depth=depth))
        assert abs(macs / 1e9 / published - 1) < 0.02, (width, depth, macs)
    # depth: every block the same 474,218,496; tokens: 128x128 gives 14 x 14 = 196,
    # linear terms by 196 / 144, the two attention matmuls by its square
    block = 474_218_496
    assert (vit_cost.multiply_adds(VIT_B)
            - vit_cost.multiply_adds(dict(VIT_B, depth=23))) == block
    grown = vit_cost.multiply_adds(dict(VIT_B, input_size=[128, 128]))
    attn = 24 * 2 * 144 * 144 * 512
    rest = vit_cost.multiply_adds(VIT_B) - attn - 512 * 512
    assert grown == rest * 196 // 144 + attn * 196 * 196 // (144 * 144) + 512 * 512


# ---- the readers, on a recorded list ----

STEP = "jit_packed_step(7)"
QK = "%fusion.11 = f32[1024,8,144,144] fusion(...)"
SOFTMAX = "%fusion.12 = bf16[1024,8,144,144] fusion(...)"
MLP = "%fusion.13 = bf16[147456,2048] fusion(...)"
OTHER_ATTN = "%fusion.14 = f32[8,144,144] fusion(...)"
KERNEL = "%streaming_match_topk.1 = custom-call"
OPS = [(1, QK, "jit(packed_step)/ocvf_embed/ViT/block0/vit_attn/nqhd,nkhd->nhqk/dot_general:"),
       (2, SOFTMAX, "jit(packed_step)/ocvf_embed/ViT/block0/vit_attn/reduce_max:"),
       (3, MLP, "jit(packed_step)/ocvf_embed/ViT/block0/vit_mlp/fc1/dot_general:"),
       # the word elsewhere than inside ocvf_embed, and as part of a longer one
       (4, OTHER_ATTN, "jit(packed_step)/ocvf_match/vit_attn/dot_general:"),
       (5, "%fusion.15 = fusion()", "jit(packed_step)/ocvf_embed/ViT/vit_attn_bias/add:"),
       (6, KERNEL, "jit(packed_step)/ocvf_match/pallas_call:")]
ATTN_METRIC = {"outer": "ocvf_embed", "inner": "vit_attn", "module": "packed_step"}


def _trace(tmp_path, ops=OPS):
    """Two runs of a 100 ms top-rung step and one of a shorter program; in
    each long run q k^T 10 ms, softmax 12 ms (2 ms of it beside q k^T), the
    MLP 40 ms, an operation that only looks like attention 5 ms, the
    matcher 25 ms."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xplane("/host:CPU", [(1, QK, "jit(f)/ocvf_embed/vit_attn/x:")])
                     + _xplane("/device:TPU:0", ops))
    events = []
    for s in (0, 150 * MS):
        events += [(QK, s + 10 * MS, 10 * MS), (SOFTMAX, s + 18 * MS, 12 * MS),
                   (MLP, s + 30 * MS, 40 * MS), (OTHER_ATTN, s + 70 * MS, 5 * MS),
                   ("%fusion.15 = fusion()", s + 5 * MS, 3 * MS),
                   (KERNEL, s + 75 * MS, 25 * MS)]
    events += [(QK, 300 * MS, 4 * MS), (MLP, 304 * MS, 10 * MS)]
    return {"file": str(path), "devices": {"/device:TPU:0": {
        "XLA Modules": [(STEP, 0, 100 * MS), (STEP, 150 * MS, 100 * MS),
                        ("jit_packed_step(9)", 300 * MS, 30 * MS)],
        "XLA Ops": events}}}


def test_inner_scope_is_read_and_the_outer_scope_still_holds_it(tmp_path):
    ctx = {"trace": _trace(tmp_path), "trace_lo": 0, "trace_hi": 340 * MS}
    # q k^T and softmax overlap by 2 ms: the union, 20 ms a top-rung step
    assert trace_inner_scope_time.read(ATTN_METRIC, ctx) == pytest.approx(20.0)
    assert ctx["notes"]["inner_scope_runs"] == {
        "ocvf_embed/vit_attn": {"runs": 2, "ops": 5}}
    # the accepted reader files every operation of the net under ocvf_embed,
    # the inner scope's too: 5-8, 10-70 ms of each step
    embed = {"scope": "ocvf_embed", "module": "packed_step"}
    assert trace_scope_time.read(embed, ctx) == pytest.approx(63.0)
    assert trace_inner_scope_time.read(dict(ATTN_METRIC, inner="vit_mlp"), ctx) \
        == pytest.approx(40.0)
    # a window that cuts the second step leaves one whole run
    cut = {"trace": ctx["trace"], "trace_lo": 0, "trace_hi": 200 * MS}
    assert trace_inner_scope_time.read(ATTN_METRIC, cut) == pytest.approx(20.0)


def test_a_program_without_the_scope_gives_nothing_never_zero(tmp_path):
    # the parent's program, or another embedder's: ocvf_embed, no vit_attn
    ops = [(1, QK, "jit(packed_step)/ocvf_embed/IResNet/stem_conv/conv_general_dilated:"),
           (6, KERNEL, "jit(packed_step)/ocvf_match/pallas_call:")]
    ctx = {"trace": _trace(tmp_path, ops), "trace_lo": 0, "trace_hi": 340 * MS}
    assert trace_inner_scope_time.read(ATTN_METRIC, ctx) is None
    assert trace_scope_time.read({"scope": "ocvf_embed", "module": "packed_step"},
                                 ctx) == pytest.approx(10.0)
    # no scope of that name inside the outer one, no such program, no trace
    full = {"trace": _trace(tmp_path), "trace_lo": 0, "trace_hi": 340 * MS}
    assert trace_inner_scope_time.read(dict(ATTN_METRIC, inner="vit_absent"), full) is None
    assert trace_inner_scope_time.read(dict(ATTN_METRIC, outer="ocvf_crop"), full) is None
    assert trace_inner_scope_time.read(dict(ATTN_METRIC, module="absent"), full) is None
    assert trace_inner_scope_time.read(ATTN_METRIC, {"trace": None}) is None
    assert trace_inner_scope_time.read(
        ATTN_METRIC, dict(full, trace_hi=90 * MS)) is None  # no whole run


def test_vit_mfu_counts_the_slots_the_program_counted():
    params = {"scope": "ocvf_embed", "slots": "embed_slots", "net": "embedder"}
    scoped = {"/device:TPU:0": {"ocvf_embed": [
        (0, 200 * MS), (50 * MS, 100 * MS), (300 * MS, 500 * MS), (600 * MS, 650 * MS)]}}

    def ctx(**over):
        out = {"trace": {"file": "unused"}, "scoped_ops": scoped, "trace_lo": 0,
               "trace_hi": 700 * MS, "peaks": peaks.DEVICE_PEAKS["TPU v5 lite"],
               "config": {"embedder": VIT_B}, "counters": {"embed_slots": 2304.0}}
        out.update(over)
        return out

    made = ctx()
    want = 100 * 2 * 11_437_170_688 * 2304 / (197e12 * 0.45)
    assert vit_mfu.read(params, made) == pytest.approx(want)
    assert made["notes"]["vit_mfu"]["device_s"] == pytest.approx(0.45)
    assert vit_mfu.read(params, ctx(counters={})) is None  # the parent: no such counter
    assert vit_mfu.read(params, ctx(scoped_ops={"/device:TPU:0": {}})) is None
    # a configuration whose embedder is another kind of net states no patch
    assert vit_mfu.read(params, ctx(config={"embedder": {"stem_features": 64}})) is None
    assert vit_mfu.read(params, ctx(config={})) is None
    assert vit_mfu.read(params, {"trace": None, "counters": {}}) is None


def test_the_new_entries_are_appended_and_name_their_readers_and_the_cell():
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert bench["configs"][-1]["name"] == "watchlist4m-vitb"
    assert bench["configs"][-1]["reduced"] == []
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "watchlist4m-vitb.crowd", "watchlist4m-vitb", "crowd", 1)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for metric, reader in zip(bench["per_layer"][-2:], ("vit_mfu", "trace_inner_scope_time")):
        assert metric["workloads"] == ["watchlist4m-vitb.crowd"]
        assert metric["moves"] == "served_fps"
        with open(os.path.join(BENCH, "layer_metrics", metric["name"] + ".json")) as fh:
            assert json.load(fh)["reader"] == reader
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "embed_vit_mfu.backlog", "attn_device_ms.backlog"]
    # the accepted share of peak stays pinned to its own cell
    pinned = next(m for m in bench["per_layer"] if m["name"] == "embed_mfu.backlog")
    assert pinned["workloads"] == ["watchlist4m-r50.crowd"]
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200 and len(entry.get("source", "")) <= 200


# ---- the rehearsal ----

ARGV = ["--workload", "tiny-vitb.trickle", "--seed", "2999000046", "--seconds", "2",
        "--trace", "0"]

#: one block's ``proj`` is not the checkpoint's: the step is handed the first
#: block's projection kernel negated (the reference reads the file's)
BROKEN_PROJ = '''
import jax as _jax
from opencv_facerecognizer_tpu.parallel import pipeline as _pipeline
_init = _pipeline.RecognitionPipeline.__init__
def _negated_proj(self, detector, embed_net, embed_params, *a, **kw):
    embed_params = dict(embed_params)
    block = dict(embed_params["block0"])
    block["proj"] = _jax.tree_util.tree_map(lambda v: -v, block["proj"])
    embed_params["block0"] = block
    _init(self, detector, embed_net, embed_params, *a, **kw)
_pipeline.RecognitionPipeline.__init__ = _negated_proj
'''

#: a slot lost where it is produced: the first slot of every frame comes
#: back not valid
BROKEN_SLOT = '''
from opencv_facerecognizer_tpu.parallel import pipeline as _pipeline
_unpack = _pipeline.unpack_result
def _one_lost(packed, top_k):
    r = _unpack(packed, top_k)
    valid = r.valid.copy()
    valid[..., 0] = False
    return r._replace(valid=valid)
_pipeline.unpack_result = _one_lost
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = rehearse.make_copy(str(tmp_path_factory.mktemp("bench_vitb")))
    for src, dst in (("tiny-vitb.json", "configs/tiny-vitb.json"),
                     ("tiny-vitb.limits.json", "configs/tiny-vitb.limits.json")):
        target = os.path.join(root, "benchmark", dst)
        assert not os.path.exists(target)
        shutil.copy(os.path.join(rehearse.FIXTURES, src), target)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tiny-vitb", "source": "rehearsal",
                             "file": "benchmark/configs/tiny-vitb.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-vitb.trickle", "config": "tiny-vitb",
                               "traffic": "trickle", "chips": 1, "why": "rehearsal"})
    for metric in bench["per_layer"]:
        if metric["name"] != "settled_share.rehearsal":
            metric["workloads"].append("tiny-vitb.trickle")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def test_the_cell_runs_from_a_seeded_vit_and_is_correct(copy):
    rc, result, err = rehearse.run_cell(copy, ARGV)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0, result["compared"]
    assert result["attempted"] > 0 and result["device"]["platform"] == "cpu"
    assert "ViT embedder drawn from seed 5" in err
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny-vitb.trickle.seed2999000046.trace0.json")) as fh:
        detail = json.load(fh)
    assert detail["setup_split_s"]["embedder_make"] > 0
    # 8-frame rung, 2 face slots, 9 tokens a 32x32 crop at patch 9
    window = detail["counters_window"]
    assert window["embed_slots"] == 16 * window["batches_dispatched"] > 0
    assert window["embed_tokens"] == 9 * window["embed_slots"]
    assert detail["judged"]["faces_compared"] > 0


@pytest.mark.parametrize("patch,failing", [(BROKEN_STEP, "sim_err"),
                                           (BROKEN_SLOT, "det_miss"),
                                           (BROKEN_PROJ, "sim_err")])
def test_broken_timed_path_is_not_correct(copy, patch, failing):
    rc, result, err = rehearse.run_cell(copy, ARGV, patch=patch)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    value, limit = result["compared"][failing]
    assert value > limit


def test_traced_rehearsal_leaves_out_what_a_cpu_trace_cannot_name(copy):
    """A CPU's operations carry no scope: the two new readers find nothing,
    say nothing and raise nothing, and the line has the rest."""
    rc, result, err = rehearse.run_cell(copy, ARGV[:-1] + ["1"],
                                        patch=rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    metrics = result["metrics"]
    for name in ("embed_vit_mfu.backlog", "attn_device_ms.backlog",
                 "embed_device_ms.backlog"):
        assert name not in metrics
    assert "batch_fill_share.backlog" in metrics and "dispatch_p50_ms.backlog" in metrics

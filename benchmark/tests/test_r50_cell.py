"""The ``watchlist4m-r50`` configuration's own files: the readers of the
step's named scopes and the IResNet cost function on a small recorded list,
and a rehearsal of its stack and its reference at CPU size (a small IResNet
made from the seed, gate and detector trained as the ``tiny`` rehearsal's):
the cell runs and is correct, and with the timed path broken underneath it
is not. Rehearsal only: no device metric is read from these."""

import json
import os
import shutil

import pytest

from benchmark import peaks
from benchmark.readers import iresnet_cost, scope_mfu, trace_scope_time
from benchmark.tests import rehearse
from benchmark.tests.test_rehearsal_cell import BROKEN_STEP

MS = 1_000_000
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one chip, two runs of a 128-frame step (100 ms: detect 5, crop 10, embed
#: 60 in two operations, one of them holding a nested one, match 25) and one
#: of a shorter program, 50 ms idle between them
STEP = "jit_packed_step(7)"
RECORDED = {"devices": {"/device:TPU:0": {
    "XLA Modules": [(STEP, 0, 100 * MS), (STEP, 150 * MS, 100 * MS),
                    ("jit_packed_step(9)", 300 * MS, 30 * MS)],
    "XLA Ops": [],
}}, "file": "unused"}
SCOPED = {"/device:TPU:0": {
    "ocvf_detect": [(s, s + 5 * MS) for s in (0, 150 * MS, 300 * MS)],
    "ocvf_crop": [(s + 5 * MS, s + 15 * MS) for s in (0, 150 * MS)] + [(305 * MS, 307 * MS)],
    "ocvf_embed": [iv for s in (0, 150 * MS) for iv in (
        (s + 15 * MS, s + 45 * MS), (s + 20 * MS, s + 30 * MS),  # nested: counted once
        (s + 45 * MS, s + 75 * MS))] + [(307 * MS, 322 * MS)],
    "ocvf_match": [(s + 75 * MS, s + 100 * MS) for s in (0, 150 * MS)],
}}
R50 = {"embed_dim": 512, "input_size": [112, 112], "in_channels": 3, "stem_features": 64,
       "stage_features": [64, 128, 256, 512], "stage_blocks": [3, 4, 14, 3]}


def _ctx(**over):
    ctx = {"trace": RECORDED, "scoped_ops": SCOPED, "trace_lo": 0, "trace_hi": 340 * MS,
           "peaks": peaks.DEVICE_PEAKS["TPU v5 lite"], "config": {"embedder": R50},
           "counters": {"embed_slots": 2 * 1024 + 256.0}}
    ctx.update(over)
    return ctx


def test_scope_time_is_the_union_of_the_scope_inside_a_top_rung_step():
    embed = {"scope": "ocvf_embed", "module": "packed_step"}
    ctx = _ctx()
    assert trace_scope_time.read(embed, ctx) == pytest.approx(60.0)
    assert trace_scope_time.read({**embed, "scope": "ocvf_crop"}, ctx) == pytest.approx(10.0)
    assert ctx["notes"]["scope_runs"] == {"ocvf_embed": 2, "ocvf_crop": 2}
    # a window that cuts the second step leaves one whole run
    assert trace_scope_time.read(embed, _ctx(trace_hi=200 * MS)) == pytest.approx(60.0)
    assert trace_scope_time.read(embed, _ctx(trace_hi=90 * MS)) is None
    # a scope nothing carries, a program no module is named like, no trace
    assert trace_scope_time.read({**embed, "scope": "ocvf_absent"}, _ctx()) is None
    assert trace_scope_time.read({**embed, "module": "absent"}, _ctx()) is None
    assert trace_scope_time.read(embed, {"trace": None}) is None
    # the parent commit's trace: operations, none of them under a scope
    assert trace_scope_time.read(embed, _ctx(scoped_ops={"/device:TPU:0": {}})) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(number, value):
    return _varint(number << 3) + _varint(value)


def _sub(number, payload):
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _xplane(name, ops):
    """One XPlane: stat metadata 300 = "tf_op", 2 = "flops", 9 = a tf_op
    referred to by id; ``ops``: [(id, operation name, tf_op or ref id)]."""
    body = _int(1, 1) + _sub(2, name.encode())
    for sid, text in ((300, "tf_op"), (2, "flops"),
                      (9, "jit(packed_step)/ocvf_match/pallas_call:")):
        body += _sub(5, _int(1, sid) + _sub(2, _int(1, sid) + _sub(2, text.encode())))
    for mid, op, tf_op in ops:
        stats = _sub(5, _int(1, 2) + _int(4, 12345678901))            # flops, an int64
        stats += _sub(5, _int(1, 2) + _varint(2 << 3 | 1) + b"\0" * 8)  # a double: skipped
        if isinstance(tf_op, str):
            stats += _sub(5, _int(1, 300) + _sub(5, tf_op.encode()))
        elif tf_op is not None:
            stats += _sub(5, _int(1, 300) + _int(7, tf_op))
        body += _sub(4, _int(1, mid) + _sub(2, _int(1, mid) + _sub(2, op.encode())
                                            + _sub(4, op[:5].encode()) + stats))
    return _sub(1, body)


def test_scopes_are_read_from_the_event_metadata_of_the_trace_file(tmp_path):
    """A small recorded file, written field by field as the profiler
    writes it: the scope of an operation is the ``tf_op`` stat of its
    event metadata, as a string or as a reference to a stat's name."""
    embed = "%fusion.1 = bf16[1024,112,112,64] fusion(...)"
    kernel = "%streaming_match_topk.1 = custom-call"
    ops = [(1, embed, "jit(packed_step)/ocvf_embed/IResNet/stem_conv/conv_general_dilated:"),
           (70000, kernel, 9),
           (3, "%copy.1 = copy()", "jit(packed_step)/convert_element_type:"),
           (4, "%nostat", None)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xplane("/host:CPU", [(1, embed, "jit(f)/ocvf_embed/x:")])
                     + _xplane("/device:TPU:0", ops))
    assert trace_scope_time.op_scopes(str(path)) == {embed: "ocvf_embed",
                                                     kernel: "ocvf_match"}
    trace = {"file": str(path), "devices": {"/device:TPU:0": {
        "XLA Modules": [(STEP, 0, 100 * MS)],
        "XLA Ops": [(embed, 10 * MS, 60 * MS), (kernel, 70 * MS, 25 * MS),
                    ("%copy.1 = copy()", 0, 1 * MS)]}}}
    ctx = {"trace": trace, "trace_lo": 0, "trace_hi": 100 * MS}
    params = {"scope": "ocvf_embed", "module": "packed_step"}
    assert trace_scope_time.read(params, ctx) == pytest.approx(60.0)
    assert trace_scope_time.read({**params, "scope": "ocvf_match"}, ctx) == pytest.approx(25.0)
    assert ctx["notes"]["scoped_ops"] == {"ocvf_embed": 1, "ocvf_match": 1}
    # the parent commit's file: operations whose tf_op names no scope
    path.write_bytes(_xplane("/device:TPU:0", [(3, embed, "jit(packed_step)/conv:")]))
    fresh = {"trace": trace, "trace_lo": 0, "trace_hi": 100 * MS}
    assert trace_scope_time.read(params, fresh) is None


def test_iresnet_cost_is_6_31_g_at_the_published_sizes():
    assert iresnet_cost.multiply_adds(R50) == 6_309_330_944
    with open(os.path.join(BENCH, "configs", "watchlist4m-r50.json")) as fh:
        config = json.load(fh)
    assert iresnet_cost.multiply_adds(config["embedder"]) == \
        config["embedder"]["multiply_adds_per_face"] == 6_309_330_944
    half = dict(R50, stage_blocks=[1, 1, 1, 1])
    assert iresnet_cost.multiply_adds(half) < 0.4 * iresnet_cost.multiply_adds(R50)


def test_scope_mfu_counts_the_slots_the_program_counted():
    params = {"scope": "ocvf_embed", "slots": "embed_slots", "net": "embedder",
              "cost": "iresnet"}
    ctx = _ctx()
    want = 100 * 2 * 6_309_330_944 * 2304 / (197e12 * 0.135)  # 60 + 60 + 15 ms
    assert scope_mfu.read(params, ctx) == pytest.approx(want)
    assert ctx["notes"]["scope_mfu"]["device_s"] == pytest.approx(0.135)
    assert scope_mfu.read(params, _ctx(counters={})) is None  # the parent: no such counter
    assert scope_mfu.read(params, _ctx(scoped_ops={"/device:TPU:0": {}})) is None
    assert scope_mfu.read(params, {"trace": None, "counters": {}}) is None


def test_the_new_metric_files_name_their_readers_and_the_cell():
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert metrics["embed_mfu.backlog"]["workloads"] == ["watchlist4m-r50.crowd"]
    assert "workloads" not in metrics["embed_device_ms.backlog"]
    assert "workloads" not in metrics["crop_device_ms.backlog"]
    for name, reader in (("embed_device_ms.backlog", "trace_scope_time"),
                         ("crop_device_ms.backlog", "trace_scope_time"),
                         ("embed_mfu.backlog", "scope_mfu")):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as fh:
            assert json.load(fh)["reader"] == reader
    cell = next(w for w in bench["workloads"] if w["name"] == "watchlist4m-r50.crowd")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("watchlist4m-r50", "crowd", 1)


# ---- the rehearsal ----

ARGV = ["--workload", "tiny-r50.trickle", "--seed", "2999000028", "--seconds", "2",
        "--trace", "0"]

#: the net served is not the net of the checkpoint: every PReLU slope the
#: step is handed is 0 (the reference reads the file's)
BROKEN_EMBED = '''
import jax as _jax
from opencv_facerecognizer_tpu.parallel import pipeline as _pipeline
_init = _pipeline.RecognitionPipeline.__init__
def _no_slopes(self, detector, embed_net, embed_params, *a, **kw):
    embed_params = _jax.tree_util.tree_map_with_path(
        lambda p, v: v * 0 if p[-1].key == "slope" else v, embed_params)
    _init(self, detector, embed_net, embed_params, *a, **kw)
_pipeline.RecognitionPipeline.__init__ = _no_slopes
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = rehearse.make_copy(str(tmp_path_factory.mktemp("bench_r50")))
    for src, dst in (("tiny-r50.json", "configs/tiny-r50.json"),
                     ("tiny-r50.limits.json", "configs/tiny-r50.limits.json")):
        target = os.path.join(root, "benchmark", dst)
        assert not os.path.exists(target)
        shutil.copy(os.path.join(rehearse.FIXTURES, src), target)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tiny-r50", "source": "rehearsal",
                             "file": "benchmark/configs/tiny-r50.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": "tiny-r50.trickle", "config": "tiny-r50",
                               "traffic": "trickle", "chips": 1, "why": "rehearsal"})
    for metric in bench["per_layer"]:
        if metric["name"] != "settled_share.rehearsal":
            metric["workloads"].append("tiny-r50.trickle")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def test_the_cell_runs_from_a_seeded_iresnet_and_is_correct(copy):
    rc, result, err = rehearse.run_cell(copy, ARGV)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0, result["compared"]
    assert result["attempted"] > 0 and result["device"]["platform"] == "cpu"
    assert "IResNet embedder drawn from seed 5" in err
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny-r50.trickle.seed2999000028.trace0.json")) as fh:
        detail = json.load(fh)
    assert detail["setup_split_s"]["embedder_make"] > 0
    # 8-frame rung, 2 face slots: every dispatched step counts 16
    window = detail["counters_window"]
    assert window["embed_slots"] == 16 * window["batches_dispatched"] > 0
    assert detail["judged"]["faces_compared"] > 0


@pytest.mark.parametrize("patch,failing", [(BROKEN_STEP, "sim_err"),
                                           (BROKEN_EMBED, "sim_err")])
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
    for name in ("embed_device_ms.backlog", "crop_device_ms.backlog", "embed_mfu.backlog"):
        assert name not in metrics
    assert "batch_fill_share.backlog" in metrics and "dispatch_p50_ms.backlog" in metrics

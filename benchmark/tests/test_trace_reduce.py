"""The reduction from a trace to numbers, on a small recorded trace."""

import json
import os

from benchmark import peaks, trace_reduce
from benchmark.readers import roofline, trace_idle_share, trace_module_time

MS = 1_000_000
KERNEL = "%streaming_match_topk.1 = (f32[1024,1]{1,0}, s32[1024,1]{1,0}) custom-call"

#: one chip, three steps of 100 ms (kernel 90 ms + a 5 ms fusion nested in a
#: 10 ms parent), 50 ms idle between steps
RECORDED = {"devices": {"/device:TPU:0": {
    "XLA Modules": [("jit_packed_step(1)", s * 150 * MS, 100 * MS) for s in range(3)]
                   + [("jit_stage1(2)", 2 * 150 * MS + 100 * MS, 1 * MS)],
    "XLA Ops": [e for s in range(3) for e in (
        (KERNEL, s * 150 * MS, 90 * MS),
        (KERNEL, s * 150 * MS, 375),  # the launch marker the chip's trace holds
        ("%fusion.parent", s * 150 * MS + 90 * MS, 10 * MS),
        ("%fusion.child", s * 150 * MS + 92 * MS, 5 * MS))],
}}, "sync_ns": 0, "shape": {}}


def _ctx():
    return {"trace": RECORDED, "trace_lo": 0, "trace_hi": 450 * MS,
            "config": {"gallery": {"rows": 8388608}, "embed_dim": 256},
            "peaks": peaks.DEVICE_PEAKS["TPU v5 lite"]}


def test_busy_idle_and_self_times():
    assert abs(trace_reduce.busy_seconds(RECORDED, 0, 450 * MS) - 0.300) < 1e-9
    assert abs(trace_idle_share.read({}, _ctx()) - 100 * 150 / 450) < 1e-9
    top = dict(trace_reduce.top_ops(RECORDED, 0, 450 * MS))
    assert abs(top[KERNEL[:64]] - 0.270) < 1e-9
    assert abs(top["%fusion.parent"] - 0.015) < 1e-9  # self time: child taken out
    assert abs(top["%fusion.child"] - 0.015) < 1e-9
    # a window that cuts the first step in half
    assert abs(trace_reduce.busy_seconds(RECORDED, 50 * MS, 450 * MS) - 0.250) < 1e-9


def test_idle_gaps_are_named_by_the_host_span_over_them():
    host = [("dispatch", 95 * MS, 140 * MS), ("publish", 140 * MS, 160 * MS),
            ("ready_wait", 240 * MS, 310 * MS)]
    gaps = trace_reduce.idle_gaps(RECORDED, 0, 450 * MS, host, n=3)
    assert [g[0] for g in gaps] == ["dispatch", "ready_wait", "none"]
    assert all(abs(g[1] - 0.050) < 1e-9 for g in gaps)


def test_module_time_takes_the_longest_program():
    params = {"match": "jit_", "pick": "longest_mean"}
    assert abs(trace_module_time.read(params, _ctx()) - 100.0) < 1e-9
    assert trace_module_time.read({"match": "absent"}, _ctx()) is None
    assert trace_module_time.read(params, {"trace": None}) is None


def test_roofline_share_and_its_bound():
    here = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(here, "layer_metrics",
                           "streaming_match_topk_roofline.backlog.json")) as fh:
        params = json.load(fh)
    ctx = _ctx()
    ops, nbytes = peaks.streaming_match_topk(1024, 8388608, 256)
    assert ops == 2 * 1024 * 8388608 * 256
    assert nbytes == 8388608 * 256 * 2 + 1024 * 256 * 4
    least, bound = peaks.least_seconds(ops, nbytes, ctx["peaks"])
    assert bound == "compute" and abs(least - ops / 197e12) < 1e-12
    share = roofline.read(params, ctx)
    assert abs(share - 100 * least / 0.090) < 1e-6
    assert share < 100
    assert ctx["notes"]["roofline_bound"] == {"compute": 3}
    # the sanitized form of the name reads the same query count
    renamed = {"devices": {"d": {"XLA Ops": [
        ("_streaming_match_topk.1____f32_1024_1__1_0", 0, 90 * MS)]}}}
    assert abs(roofline.read(params, {**ctx, "trace": renamed}) - share) < 1e-6
    # nothing to read: nothing returned, never 0
    assert roofline.read(params, {**ctx, "trace": {"devices": {"d": {"XLA Ops": []}}}}) is None
    assert roofline.read(params, {**ctx, "trace": None}) is None

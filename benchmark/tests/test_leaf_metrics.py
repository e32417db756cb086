"""The readers of the program's leaf spans and busy-time counters, on
hand-made lists; and the traced CPU rehearsal, which has to see the counter
metrics come out and find the program's ``ocvf:`` annotations in its trace.
Rehearsal only: no device metric is read from these."""

import json
import os

import pytest

from benchmark.readers import counter_quotient, trace_idle_under
from benchmark.tests import rehearse

MS = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))

#: one chip, three steps of 100 ms with 50 ms idle after each (at 100-150,
#: 250-300, 400-450 ms)
OPS = {"/device:TPU:0": [("step", s * 150 * MS, 100 * MS) for s in range(3)]}
#: the host, in each idle gap: 10 ms of the step's tail under gate_wait and
#: 10 ms idle under it, 15 ms under compact, 20 ms under step_enqueue (5 ms
#: of it once the next step runs), 10 ms under nothing; a publish on another
#: thread overlaps the compact
NOTES = {
    "ocvf:gate_wait": [(g - 10 * MS, g + 10 * MS) for g in (100 * MS, 250 * MS, 400 * MS)],
    "ocvf:compact": [(g + 10 * MS, g + 25 * MS) for g in (100 * MS, 250 * MS, 400 * MS)],
    "ocvf:step_enqueue": [(g + 35 * MS, g + 55 * MS) for g in (100 * MS, 250 * MS)]
                         + [(435 * MS, 450 * MS)],
    "ocvf:publish": [(105 * MS, 130 * MS)],
}
LEAVES = ["ocvf:gate_wait", "ocvf:compact", "ocvf:settle_early",
          "ocvf:upload", "ocvf:step_enqueue", "ocvf:pop_wait"]


def _share(names, inverse=False, ops=OPS, lo=0, hi=450 * MS):
    return trace_idle_under.idle_share(ops, NOTES, names, lo, hi, inverse=inverse)


def test_idle_under_is_idle_intersected_with_the_annotations_union():
    assert _share(["ocvf:gate_wait"]) == pytest.approx(100 * 30 / 450)
    assert _share(["ocvf:compact"]) == pytest.approx(100 * 45 / 450)
    assert _share(["ocvf:step_enqueue"]) == pytest.approx(100 * 45 / 450)
    assert _share(["ocvf:settle_early"]) == 0.0  # held nowhere: 0 under it
    # two names: the union of their intervals, overlap counted once
    assert _share(["ocvf:compact", "ocvf:publish"]) == pytest.approx(100 * 55 / 450)


def test_the_named_shares_and_the_rest_add_up_to_the_idle_share():
    named = sum(_share([name]) for name in LEAVES)
    rest = _share(LEAVES, inverse=True)
    assert rest == pytest.approx(100 * 30 / 450)  # 10 ms a gap under no leaf
    assert named + rest == pytest.approx(100 * 150 / 450)
    # a window that cuts into the first gap
    assert _share(["ocvf:compact"], lo=120 * MS) == pytest.approx(100 * 35 / 330)
    assert (sum(_share([n], lo=120 * MS) for n in LEAVES)
            + _share(LEAVES, inverse=True, lo=120 * MS)
            == pytest.approx(100 * 130 / 330))


def test_idle_under_averages_over_the_chips_that_ran_anything():
    two = {**OPS, "/device:TPU:1": [("step", 0, 450 * MS)], "/device:TPU:2": []}
    assert _share(["ocvf:compact"], ops=two) == pytest.approx(100 * 45 / 450 / 2)
    assert _share(LEAVES, inverse=True, ops={"/device:TPU:2": []}) is None


def test_idle_under_reads_nothing_without_a_trace_or_annotations(tmp_path):
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                           "idle_elsewhere_share.backlog.json")) as fh:
        elsewhere = json.load(fh)
    assert elsewhere["not_under"] == LEAVES
    trace = {"devices": {"d": {"XLA Ops": OPS["/device:TPU:0"]}}, "file": "unused"}
    ctx = {"trace": trace, "trace_lo": 0, "trace_hi": 450 * MS}
    assert trace_idle_under.read(elsewhere, {"trace": None}) is None
    assert trace_idle_under.read(elsewhere, {"trace": {"devices": {}}}) is None
    # a trace the program wrote no annotation into (the parent commit's)
    assert trace_idle_under.read(elsewhere, {**ctx, "host_annotations": {}}) is None
    assert trace_idle_under.read({"under": LEAVES[:1]},
                                 {**ctx, "host_annotations": {}}) is None
    # parsed once a run: the second metric finds what the first one loaded
    ctx["host_annotations"] = NOTES
    assert trace_idle_under.read(elsewhere, ctx) == pytest.approx(100 * 30 / 450)
    assert trace_idle_under.read({"under": ["ocvf:upload"]}, ctx) == 0.0
    assert ctx["notes"]["idle_under"]["ocvf:publish"] == pytest.approx(100 * 25 / 450)


def test_counter_quotient_and_its_nothing_found_cases():
    assert counter_quotient.quotient([0.5, 0.25], [100.0, 50.0], 1000) == 5.0
    assert counter_quotient.quotient([0.5], [0.0], 1000) is None
    assert counter_quotient.quotient([0.5], [None], 1000) is None
    assert counter_quotient.quotient([None], [100.0], 1000) is None  # no such counter
    params = {"numerator": ["intake_s"], "denominator": ["frames_admitted"],
              "scale": 1000}
    assert counter_quotient.read(params, {"counters": {
        "intake_s": 0.3, "frames_admitted": 1200.0}}) == pytest.approx(0.25)
    # the parent commit: frames are admitted, nothing counts their seconds
    assert counter_quotient.read(params, {"counters": {"frames_admitted": 1200.0}}) is None
    assert counter_quotient.read(params, {"counters": {"intake_s": 0.3}}) is None


def test_every_new_metric_file_names_a_reader_that_is_there():
    import importlib

    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for metric in bench["per_layer"]:
        with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                               metric["name"] + ".json")) as fh:
            params = json.load(fh)
        assert hasattr(importlib.import_module(
            "benchmark.readers." + params["reader"]), "read"), metric["name"]


def test_traced_rehearsal_reads_the_counters_and_finds_the_annotations(tmp_path):
    copy = rehearse.make_copy(str(tmp_path))
    argv = ["--workload", "tiny.still", "--seed", "2999000007", "--seconds", "2",
            "--trace", "1"]
    rc, result, err = rehearse.run_cell(copy, argv, patch=rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    metrics = result["metrics"]
    for name in ("intake_ms_per_frame.backlog", "track_cache_ms_per_batch.backlog",
                 "publish_ms_per_frame.backlog", "track_update_ms_per_frame.backlog"):
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "ms", name
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny.still.seed2999000007.trace1.json")) as fh:
        detail = json.load(fh)
    # the program's annotations are events of the profiler's own trace
    found = set(detail["notes"]["idle_under"])
    assert {"ocvf:gate_wait", "ocvf:upload", "ocvf:step_enqueue", "ocvf:publish",
            "ocvf:track_update", "ocvf:pop_wait"} <= found, found
    for leaf in LEAVES:
        assert f"idle_under_{leaf[len('ocvf:'):]}_share.backlog" in metrics
    assert "idle_elsewhere_share.backlog" in metrics
    # the accepted metrics still read, and the spans still name the gaps
    assert "dispatch_p50_ms.backlog" in metrics and result["breakdown"]["idle_gaps"]
    loop = {k: v for k, v in detail["counters_window"].items() if k.startswith("loop_s_")}
    assert loop["loop_s_gate_wait"] > 0 and detail["counters_window"]["loop_batches"] > 0

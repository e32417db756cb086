"""The six readings of the threads' CPU clocks and the batcher's lock
(PR 41), at CPU size: the traced rehearsal has to find all six in its result
line, with every CPU counter under its wall twin. (The reader's arithmetic
and the six files are held by ``tests/test_cpu_clock.py``.) Rehearsal only:
no device metric is read from these, and the milliseconds are a CPU host's.
"""

import json
import os

import pytest

from benchmark.tests import rehearse

SIX = ("intake_cpu_ms_per_frame.backlog", "intake_offcpu_ms_per_frame.backlog",
       "publish_offcpu_ms_per_frame.backlog", "loop_offcpu_ms_per_batch.backlog",
       "host_cpu_share.backlog", "batcher_lock_wait_ms_per_batch.backlog")


def test_traced_rehearsal_finds_the_six_readings(tmp_path):
    copy = rehearse.make_copy(str(tmp_path))
    argv = ["--workload", "tiny.still", "--seed", "2999000041", "--seconds", "2",
            "--trace", "1"]
    rc, result, err = rehearse.run_cell(copy, argv, patch=rehearse.CPU_TRACE_PATCH)
    assert rc == 0, err[-3000:]
    metrics = result["metrics"]
    for name in SIX:
        assert name in metrics, (name, sorted(metrics))
        unit = "%" if name == "host_cpu_share.backlog" else "ms"
        assert metrics[name]["value"] >= 0 and metrics[name]["unit"] == unit
    for name in ("intake_cpu_ms_per_frame.backlog", "host_cpu_share.backlog",
                 "batcher_lock_wait_ms_per_batch.backlog"):
        assert metrics[name]["value"] > 0, name
    with open(os.path.join(copy, ".bench_work", "out",
                           "tiny.still.seed2999000041.trace1.json")) as fh:
        window = json.load(fh)["counters_window"]
    assert 0 < window["publish_cpu_s"] <= window["publish_s"]
    assert window["publish_cpu_s"] <= window["readback_cpu_s"] * 1.05
    loop_wall = sum(v for k, v in window.items() if k.startswith("loop_s_"))
    assert 0 < window["loop_cpu_s"] <= loop_wall
    # (a backlog kept full may never make ``get_batch`` wait: no delta then)
    assert window.get("batcher_pop_wait_s", 0.0) <= window["loop_s_pop_wait"] * 1.05
    assert window["intake_thread_cpu_s"] > 0
    # the reading is the files' arithmetic over that window
    assert metrics["publish_offcpu_ms_per_frame.backlog"]["value"] == pytest.approx(
        1000 * (window["publish_s"] - window["publish_cpu_s"])
        / window["frames_completed"])
    # one acquisition a put and one a pop, handed over a batch late at most
    assert window["batcher_lock_acquires"] >= 0.9 * window["frames_admitted"]

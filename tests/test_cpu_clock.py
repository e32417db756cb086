"""The CPU clock beside the wall clock (ISSUE 41), off the serving path:
the benchmark's reader of a wall-less-CPU gap, the six metric files that
read the new counters, ``Metrics.incr_many`` and the lint rule that checks
the names handed to it, and what left with the stage-attribution exporter.
The served-run side is in ``tests/test_tracing.py``.
"""

import importlib
import inspect
import json
import os
import sys
import threading

import numpy as np
import pytest

from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEAVES = list(mn.LOOP_LEAVES) + ["unnamed"]
#: the loop's leaves outside the two that only wait for the chip
BUSY = [leaf for leaf in LEAVES if leaf not in ("gate_wait", "inflight_wait")]

#: metric -> (layer, unit, reader, what it reads)
SIX = {
    "intake_cpu_ms_per_frame.backlog": (
        "connector / intake", "ms", "counter_quotient",
        {"numerator": [mn.INTAKE_THREAD_CPU_S],
         "denominator": [mn.FRAMES_ADMITTED], "scale": 1000}),
    "intake_offcpu_ms_per_frame.backlog": (
        "connector / intake", "ms", "counter_gap_quotient",
        {"minuend": [mn.INTAKE_S], "subtrahend": [mn.INTAKE_THREAD_CPU_S],
         "denominator": [mn.FRAMES_ADMITTED], "scale": 1000}),
    "publish_offcpu_ms_per_frame.backlog": (
        "service loop", "ms", "counter_gap_quotient",
        {"minuend": [mn.PUBLISH_S], "subtrahend": [mn.PUBLISH_CPU_S],
         "denominator": [mn.FRAMES_COMPLETED], "scale": 1000}),
    "loop_offcpu_ms_per_batch.backlog": (
        "service loop", "ms", "counter_gap_quotient",
        {"minuend": [mn.LOOP_S_PREFIX + leaf for leaf in BUSY],
         "subtrahend": [mn.BATCHER_POP_WAIT_S, mn.LOOP_CPU_S],
         "denominator": [mn.LOOP_BATCHES], "scale": 1000}),
    "host_cpu_share.backlog": (
        "service loop", "%", "counter_ratio",
        {"numerator": [mn.LOOP_CPU_S, mn.READBACK_CPU_S,
                       mn.INTAKE_THREAD_CPU_S],
         "denominator": [mn.LOOP_S_PREFIX + leaf for leaf in LEAVES]}),
    "batcher_lock_wait_ms_per_batch.backlog": (
        "batcher", "ms", "counter_quotient",
        {"numerator": [mn.BATCHER_LOCK_WAIT_S],
         "denominator": [mn.LOOP_BATCHES], "scale": 1000}),
}


def _registered(counter: str) -> bool:
    if counter in mn.all_names():
        return True
    return (counter.startswith(mn.LOOP_S_PREFIX)
            and counter[len(mn.LOOP_S_PREFIX):] in LEAVES)


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as fh:
        spec = json.load(fh)
    return spec, importlib.import_module("benchmark.readers." + spec["reader"])


@pytest.mark.parametrize("metric", sorted(SIX))
def test_cpu_clock_metric_is_declared_once_and_reads_registered_counters(
        metric):
    layer, unit, reader, reads = SIX[metric]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (declared,) = [m for m in bench["per_layer"] if m["name"] == metric]
    # no ``workloads`` list: every cell reports it
    assert declared == {
        "name": metric, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": layer, "moves": "served_fps"}
    # appended: behind every metric the benchmark had
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(metric) > names.index("merge_device_ms.backlog")
    spec, module = _spec(metric)
    assert spec.pop("reader") == reader and spec.pop("what")
    assert spec == reads and hasattr(module, "read")
    counters = [c for key, value in reads.items() if key != "scale"
                for c in value]
    assert counters and all(_registered(c) for c in counters), counters


def test_a_window_reads_through_the_six_files_and_a_parents_reads_nothing():
    """A window as a run's ``counters_window`` holds it: the readings that
    the arithmetic of the files' lists gives; and the parent commit's
    window, wall counters alone."""
    window = {mn.FRAMES_ADMITTED: 2000.0, mn.FRAMES_COMPLETED: 1000.0,
              mn.LOOP_BATCHES: 20.0, mn.INTAKE_S: 0.5,
              mn.INTAKE_THREAD_CPU_S: 0.3, mn.PUBLISH_S: 0.4,
              mn.PUBLISH_CPU_S: 0.25, mn.READBACK_CPU_S: 0.3,
              mn.LOOP_CPU_S: 0.35, mn.BATCHER_POP_WAIT_S: 0.06,
              mn.BATCHER_LOCK_WAIT_S: 0.01, mn.BATCHER_LOCK_ACQUIRES: 2020.0}
    for leaf in LEAVES:
        window[mn.LOOP_S_PREFIX + leaf] = 0.1
    expected = {
        "intake_cpu_ms_per_frame.backlog": 0.15,
        "intake_offcpu_ms_per_frame.backlog": 0.1,
        "publish_offcpu_ms_per_frame.backlog": 0.15,
        "loop_offcpu_ms_per_batch.backlog": 1000 * (9 * 0.1 - 0.06 - 0.35) / 20,
        "host_cpu_share.backlog": 100 * (0.35 + 0.3 + 0.3) / (11 * 0.1),
        "batcher_lock_wait_ms_per_batch.backlog": 0.5,
    }
    assert set(expected) == set(SIX)
    parent = {k: v for k, v in window.items()
              if "cpu_s" not in k and not k.startswith("batcher_")}
    for metric, value in expected.items():
        spec, module = _spec(metric)
        assert module.read(spec, {"counters": window}) == pytest.approx(value)
        reading = module.read(spec, {"counters": parent})
        if metric == "host_cpu_share.backlog":
            assert reading == 0.0  # counter_ratio's rule: 0 over a wall time
        else:
            assert reading is None, metric


@pytest.mark.parametrize("minuend, subtrahend, denominator, expected", [
    ([0.5, 0.25], [0.25, 0.2], [100.0, 50.0], 2.0),   # a reading
    ([0.5], [None], [100.0], None),      # no CPU counter: the parent commit
    ([0.5], [0.25], [None], None),       # nothing to divide by
    ([0.5], [0.25], [0.0], None),
    ([0.5], [0.5000001], [100.0], 0.0),  # CPU a hair over wall: 0, not < 0
    ([None], [0.25], [100.0], 0.0),      # a wall counter that did not move
])
def test_counter_gap_quotient(minuend, subtrahend, denominator, expected):
    from benchmark.readers import counter_gap_quotient

    got = counter_gap_quotient.gap_quotient(minuend, subtrahend, denominator,
                                            1000)
    assert got == (None if expected is None else pytest.approx(expected))
    names = {"minuend": [f"m{i}" for i in range(len(minuend))],
             "subtrahend": [f"s{i}" for i in range(len(subtrahend))],
             "denominator": [f"d{i}" for i in range(len(denominator))],
             "scale": 1000}
    window = {name: value
              for key, values in (("minuend", minuend),
                                  ("subtrahend", subtrahend),
                                  ("denominator", denominator))
              for name, value in zip(names[key], values) if value is not None}
    assert counter_gap_quotient.read(names, {"counters": window}) == got


def test_incr_many_counts_every_pair_under_one_acquisition():
    metrics = Metrics()
    acquisitions = []

    class Counting:
        def __init__(self, lock):
            self._lock = lock

        def __enter__(self):
            acquisitions.append(threading.get_ident())
            return self._lock.__enter__()

        def __exit__(self, *exc):
            return self._lock.__exit__(*exc)

    metrics._lock = Counting(metrics._lock)
    metrics.incr_many((mn.PUBLISH_S, 0.5), (mn.PUBLISH_CPU_S, 0.25),
                      (mn.READBACK_CPU_S, 0.125))
    metrics.incr_many((mn.PUBLISH_S, 0.5))
    assert len(acquisitions) == 2
    assert metrics.counters() == {mn.PUBLISH_S: 1.0, mn.PUBLISH_CPU_S: 0.25,
                                  mn.READBACK_CPU_S: 0.125}


def test_batcher_lock_counts_lose_no_acquisition_under_contention():
    """Eight producers against one consumer, the interpreter switching
    every 10 us: the sums live in plain attributes under the lock they
    count, so every ``put`` and every ``get_batch`` that popped is in."""
    from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher

    metrics = Metrics()
    batcher = FrameBatcher(4, (4, 4), flush_timeout=0.005, max_pending=1 << 16,
                           dtype=np.uint8, metrics=metrics)
    frame = np.zeros((4, 4), np.uint8)
    producers, each = 8, 250
    pops = []

    def produce():
        for _ in range(each):
            assert batcher.put(frame)

    calls = []

    def consume():
        seen = 0
        while seen < producers * each:
            batch = batcher.get_batch(block=True)
            calls.append(1)
            if batch is not None:
                seen += batch.count
                pops.append(batch.count)

    threads = [threading.Thread(target=produce) for _ in range(producers)]
    threads.append(threading.Thread(target=consume))
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60.0)
    finally:
        sys.setswitchinterval(before)
    assert not any(thread.is_alive() for thread in threads)
    assert sum(pops) == producers * each
    # one a put and one a call of get_batch, exactly; an empty-handed
    # call hands nothing over, its acquisition rides with the next batch
    handed = metrics.counter(mn.BATCHER_LOCK_ACQUIRES) + batcher._lock_acquires
    assert handed == producers * each + len(calls)
    assert metrics.counter(mn.BATCHER_LOCK_ACQUIRES) >= producers * each + 1
    assert metrics.counter(mn.BATCHER_LOCK_WAIT_S) >= 0.0


def test_the_stage_attribution_exporter_is_gone():
    from opencv_facerecognizer_tpu.runtime import expo, promtext

    for name in ("fold_attribution", "load_stage_quotes", "DEVICE_STAGES",
                 "DEFAULT_BENCH_PATH"):
        assert not hasattr(expo, name), name
    assert "bench_path" not in inspect.signature(
        expo.ExpoServer.__init__).parameters
    assert not hasattr(mn, "STAGE_SHARE_PREFIX")
    assert "stage_share_" not in mn.all_prefixes()
    server = expo.ExpoServer(metrics=Metrics(), port=0)
    try:
        assert "/attribution" not in server.payload("/", {})["endpoints"]
        with pytest.raises(KeyError):
            server.payload("/attribution", {})
    finally:
        server._httpd.server_close()
    # a gauge by the old family's name is a plain gauge now, one family
    metrics = Metrics()
    metrics.set_gauge("stage_share_b8_detect", 0.5)  # ocvf-lint: disable=metrics-registry -- the retired family's name, on purpose
    text = promtext.render(metrics)
    assert "ocvf_stage_share_b8_detect 0.5" in text
    assert promtext.lint_prometheus_text(text) == []

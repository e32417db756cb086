"""What PR 41's second clock costs, timed alone on the host it is run on
(no JAX, no device: the chip's host is what matters, its cores and its
kernel's ``clock_gettime``):

    chiprun -- python3 benchmark/tests/chip_clock_cost.py

Every primitive in a loop of its own, best of several repeats, then the
sums the program adds a frame on the handler's thread and a batch on the
loop's and the readback worker's, by the count of what each path gained
(``runtime/recognizer.py``, ``runtime/batcher.py``), and what the design
built first would have added (two reads a leaf, four a frame). Prints one JSON line
and writes it to ``chiprun_out/clock_cost.json``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from opencv_facerecognizer_tpu.utils import metric_names as mn  # noqa: E402
from opencv_facerecognizer_tpu.utils import tracing  # noqa: E402
from opencv_facerecognizer_tpu.utils.metrics import Metrics  # noqa: E402

N = 200_000


class WallLeaf:
    """The loop's leaf as it is: one clock, one dict."""

    __slots__ = ("_busy", "_stage", "_span", "_t0")

    def __init__(self, busy, stage, span):
        self._busy, self._stage, self._span = busy, stage, span

    def __enter__(self):
        self._t0 = time.monotonic()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._busy[self._stage] = (self._busy.get(self._stage, 0.0)
                                   + time.monotonic() - self._t0)
        return False


class BothLeaf(WallLeaf):
    """The leaf with a CPU clock read beside the wall clock: what this PR
    built first, and took out for what this script read."""

    __slots__ = ("_cpu", "_c0")

    def __init__(self, busy, cpu, stage, span):
        WallLeaf.__init__(self, busy, stage, span)
        self._cpu = cpu

    def __enter__(self):
        self._t0 = time.monotonic()
        self._c0 = time.thread_time()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._cpu[self._stage] = (self._cpu.get(self._stage, 0.0)
                                  + time.thread_time() - self._c0)
        self._busy[self._stage] = (self._busy.get(self._stage, 0.0)
                                   + time.monotonic() - self._t0)
        return False


def best_us(fn, repeats=7) -> float:
    """Microseconds a call of ``fn(n)``'s loop body, best of ``repeats``."""
    return min(timeit.repeat(lambda: fn(N), number=1, repeat=repeats)) / N * 1e6


def main() -> int:
    metrics = Metrics()
    busy, cpu = {}, {}
    null = tracing.NULL_SPAN

    def empty(n):
        for _ in range(n):
            pass

    def monotonic(n, f=time.monotonic):
        for _ in range(n):
            f()

    def thread_time(n, f=time.thread_time):
        for _ in range(n):
            f()

    def incr(n, f=metrics.incr, name=mn.INTAKE_S):
        for _ in range(n):
            f(name, 0.5)

    def incr_many2(n, f=metrics.incr_many, a=mn.PUBLISH_S, b=mn.PUBLISH_CPU_S):
        for _ in range(n):
            f((a, 0.5), (b, 0.25))

    def incr_many3(n, f=metrics.incr_many, a=mn.PUBLISH_S, b=mn.PUBLISH_CPU_S,
                   c=mn.READBACK_CPU_S):
        for _ in range(n):
            f((a, 0.5), (b, 0.25), (c, 0.125))

    def wall_leaf(n):
        for _ in range(n):
            with WallLeaf(busy, "compact", null):
                pass

    def both_leaf(n):
        for _ in range(n):
            with BothLeaf(busy, cpu, "compact", null):
                pass

    marks = {threading.get_ident(): [0, 0.0]}

    def mark(n, get_ident=threading.get_ident):
        for _ in range(n):
            m = marks.get(get_ident())
            m[0] += 1
            if m[0] >= 1 << 60:
                pass

    loop = best_us(empty)
    us = {name: best_us(fn) - loop for name, fn in (
        ("monotonic", monotonic), ("thread_time", thread_time),
        ("incr", incr), ("incr_many_2", incr_many2),
        ("incr_many_3", incr_many3), ("leaf_wall_only", wall_leaf),
        ("leaf_both_clocks", both_leaf), ("mark", mark))}
    leaf = us["leaf_both_clocks"] - us["leaf_wall_only"]
    every = 128  # recognizer.INTAKE_CPU_EVERY
    # the handler, a frame: its thread's mark looked up and counted, one
    # read of the CPU clock and one counter in ``every`` frames, and the
    # clock pair round the batcher's lock
    frame = (us["mark"] + (us["thread_time"] + us["incr"]) / every
             + 2 * us["monotonic"])
    # the loop, a batch: the iteration's one read, its counter folded into
    # the call that counted the batch, the clock pair round ``get_batch``'s
    # acquire and round a condition wait or two, their one hand-over
    batch_loop = (us["thread_time"] + us["incr_many_2"] - us["incr"]
                  + 6 * us["monotonic"] + us["incr_many_3"])
    # the worker, a batch: ``_publish``'s two reads, three counters in the
    # call that counted one
    batch_worker = 2 * us["thread_time"] + us["incr_many_3"] - us["incr"]
    # what was built first and taken out: two reads a leaf, four a frame
    first_frame = 4 * us["thread_time"] + us["incr_many_3"] - us["incr"]
    first_loop = 11 * leaf + us["thread_time"]
    out = {"primitives_us": {k: round(v, 4) for k, v in us.items()},
           "added_us": {"handler_a_frame": round(frame, 3),
                        "loop_a_batch": round(batch_loop, 3),
                        "worker_a_batch": round(batch_worker, 3)},
           "first_design_us": {"handler_a_frame": round(first_frame, 3),
                               "loop_a_batch": round(first_loop, 3),
                               "a_leaf": round(leaf, 3),
                               "a_tracked_frame": round(2 * us["thread_time"], 3)},
           "budget_us": {"handler_a_frame": 1.5, "loop_a_batch": 15.0,
                         "worker_a_batch": 15.0},
           "host": {"cpus": os.cpu_count(),
                    "switch_interval_s": sys.getswitchinterval()}}
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "clock_cost.json"), "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

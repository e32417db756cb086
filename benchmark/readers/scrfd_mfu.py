"""Share of the chip's bf16 peak that the detector's forward reaches, in
percent: 2 x multiply-adds of the configured SCRFD a frame
(``scrfd_cost.multiply_adds`` of the configuration's ``detector`` entry) x
the frames the program counted through the detector in the window, over
the peak (``benchmark/peaks.py``) times the device seconds of the window in
which an operation of the scope ran (every rung's steps, not the top rung's
alone: the counter counts them all). ``scope_mfu`` for a net whose unit of
work is a frame; it shares that reader's ``seconds_under``.

Parameters: ``scope``; ``frames`` (the program's counter of frames through
the detector); ``net`` (the configuration's entry the cost is counted
from). The counter is read at the window's edges and the operations inside
them, so a step in flight at an edge is counted on one side only: under 2 %
at 60 steps a window. No trace, no such counter, no such entry in the
configuration or no operation under the scope: nothing.
"""

from benchmark.readers import scrfd_cost, trace_scope_time


def read(params, ctx):
    scoped = trace_scope_time.scoped_ops(ctx)
    frames = ctx["counters"].get(params["frames"])
    net = ctx.get("config", {}).get(params["net"])
    if not scoped or not frames or not net:
        return None
    seconds = trace_scope_time.seconds_under(
        scoped, params["scope"], ctx["trace_lo"], ctx["trace_hi"])
    if not seconds:
        return None
    flops = 2.0 * scrfd_cost.multiply_adds(net) * frames
    ctx.setdefault("notes", {})["scrfd_mfu"] = {
        "frames": frames, "device_s": seconds, "tflop": flops / 1e12}
    return 100.0 * flops / (ctx["peaks"]["bf16_tflops"] * 1e12 * seconds)

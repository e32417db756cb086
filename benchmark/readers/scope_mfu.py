"""Share of the chip's bf16 peak that the operations under one
``jax.named_scope`` reach, in percent: 2 x multiply-adds of the configured
net x the face slots the program counted through it in the window, over
the peak (``benchmark/peaks.py``) times the device seconds of the window
in which an operation of the scope ran (every rung's steps, not the top
rung's alone: the counter counts them all).

Parameters: ``scope``; ``slots`` (the program's counter of units of work);
``net`` (the configuration's entry that ``cost`` counts from); ``cost``
(a function of ``COSTS``: multiply-adds of one unit). The counter is read
at the window's edges and the operations inside them, so a step in flight
at an edge is counted on one side only: under 2 % at 60 steps a window.
No trace, no such counter or no operation under the scope: nothing.
"""

from benchmark.readers import iresnet_cost, trace_scope_time

COSTS = {"iresnet": iresnet_cost.multiply_adds}


def read(params, ctx):
    scoped = trace_scope_time.scoped_ops(ctx)
    slots = ctx["counters"].get(params["slots"])
    if not scoped or not slots:
        return None
    seconds = trace_scope_time.seconds_under(
        scoped, params["scope"], ctx["trace_lo"], ctx["trace_hi"])
    if not seconds:
        return None
    flops = 2.0 * COSTS[params["cost"]](ctx["config"][params["net"]]) * slots
    ctx.setdefault("notes", {})["scope_mfu"] = {
        "slots": slots, "device_s": seconds, "tflop": flops / 1e12}
    return 100.0 * flops / (ctx["peaks"]["bf16_tflops"] * 1e12 * seconds)

"""Share of the chip's bf16 peak that the vision-transformer embedder
reaches, in percent: 2 x multiply-adds of the configured net a face
(``vit_cost.multiply_adds`` of the configuration's ``embedder`` entry) x the
face slots the program counted through the embedder in the window, over the
peak (``benchmark/peaks.py``) times the device seconds of the window in
which an operation of the scope ran (every rung's steps, not the top rung's
alone: the counter counts them all). ``scope_mfu``'s arithmetic for a net
that reader's ``COSTS`` does not hold; it shares ``seconds_under``.

Parameters: ``scope``; ``slots`` (the program's counter of face slots);
``net`` (the configuration's entry the cost is counted from). The counter is
read at the window's edges and the operations inside them, so a step in
flight at an edge is counted on one side only: under 3 % at 35 steps a
window. No trace, no such counter, no such entry in the configuration (or
one that states no ``patch``: another kind of net) or no operation under the
scope: nothing.
"""

from benchmark.readers import trace_scope_time, vit_cost


def read(params, ctx):
    scoped = trace_scope_time.scoped_ops(ctx)
    slots = ctx["counters"].get(params["slots"])
    net = ctx.get("config", {}).get(params["net"])
    if not scoped or not slots or not net or "patch" not in net:
        return None
    seconds = trace_scope_time.seconds_under(
        scoped, params["scope"], ctx["trace_lo"], ctx["trace_hi"])
    if not seconds:
        return None
    flops = 2.0 * vit_cost.multiply_adds(net) * slots
    ctx.setdefault("notes", {})["vit_mfu"] = {
        "slots": slots, "device_s": seconds, "tflop": flops / 1e12}
    return 100.0 * flops / (ctx["peaks"]["bf16_tflops"] * 1e12 * seconds)

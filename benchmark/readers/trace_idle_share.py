"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of the device operations' intervals) / window,
averaged over the chips used. No trace: nothing is returned.
"""

from benchmark import trace_reduce


def read(params, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    busy = trace_reduce.busy_seconds(trace, ctx["trace_lo"], ctx["trace_hi"])
    if busy is None:
        return None
    window = (ctx["trace_hi"] - ctx["trace_lo"]) / 1e9
    return 100.0 * (1.0 - busy / window)

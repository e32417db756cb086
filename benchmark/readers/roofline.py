"""A kernel's share of its roofline, in percent: the least time the chip
could take for the kernel's calls (by ``benchmark/peaks.py``: the larger
of operations over peak FLOP/s and bytes over peak bytes/s) over the
device time of its events in the trace.

Parameters: ``match`` (part of the operation's name in the trace),
``cost`` (a function of ``peaks.COST_FUNCTIONS``, called with q, n, d),
``q_pattern`` (a regular expression whose first group is the number of
queries in the event's name), ``n`` and ``d`` (keys of the configuration
file, dotted). An event whose query count cannot be read is left out of
both sums, and so is one shorter than a thousandth of the least time
(the kernel's launch marker). No trace, or no such event: nothing is
returned, never 0.
"""

import re

from benchmark import peaks, trace_reduce


def _dig(config, dotted):
    value = config
    for part in dotted.split("."):
        value = value[part]
    return value


def read(params, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    cost = peaks.COST_FUNCTIONS[params["cost"]]
    n = int(_dig(ctx["config"], params["n"]))
    d = int(_dig(ctx["config"], params["d"]))
    pattern = re.compile(params["q_pattern"])
    least, spent, bound, durations = 0.0, 0.0, {}, []
    for events in trace_reduce.op_events(trace).values():
        for name, _start, dur in trace_reduce.clip(
                events, ctx["trace_lo"], ctx["trace_hi"]):
            if params["match"] not in name:
                continue
            found = pattern.search(name)
            if not found:
                continue
            seconds, which = peaks.least_seconds(
                *cost(int(found.group(1)), n, d), ctx["peaks"])
            if dur / 1e9 < seconds / 1000.0:
                # The trace holds, beside each run of the kernel, an event of
                # the same name a few hundred nanoseconds long (its launch).
                # Nothing runs the kernel in a thousandth of its least time.
                continue
            least += seconds
            spent += dur / 1e9
            bound[which] = bound.get(which, 0) + 1
            durations.append(dur)
    if spent <= 0:
        return None
    ctx.setdefault("notes", {})["roofline_bound"] = bound
    ctx["notes"]["roofline_event_ns"] = sorted(durations)[:3] + sorted(durations)[-3:]
    return 100.0 * least / spent

"""Device time of one run of a compiled program, in milliseconds, from
the profiler trace's "XLA Modules" line: the mean duration of the events
whose name contains ``match``. Where several programs match (one per
batch rung), the one whose mean is longest is taken: the top rung. No
trace, or no such event: nothing is returned.
"""

from collections import defaultdict

from benchmark import trace_reduce


def read(params, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    groups = defaultdict(list)
    for lines in trace["devices"].values():
        events = trace_reduce.clip(lines.get(trace_reduce.MODULES_LINE, []),
                                   ctx["trace_lo"], ctx["trace_hi"])
        for name, _start, dur in events:
            if params["match"] in name:
                groups[name].append(dur)
    if not groups:
        return None
    means = {name: sum(d) / len(d) for name, d in groups.items()}
    name = max(means, key=means.get)
    ctx.setdefault("notes", {})[params.get("note", "module")] = {
        "name": name[:80], "runs": len(groups[name])}
    return means[name] / 1e6

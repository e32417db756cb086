"""Device time a top-rung step spends under a scope the program names
INSIDE one of the step's ``ocvf_<stage>`` scopes, in milliseconds: the
union of the intervals of the device operations whose ``tf_op`` is filed
under ``outer`` (the first ``ocvf_<stage>`` in it, as ``trace_scope_time``
files it, so that the outer scope's own metric goes on holding them) and
also holds the name ``inner`` as a whole word, inside each run of the step's
program on the "XLA Modules" line, mean over the runs that lie whole in the
window. A fusion carries the ``tf_op`` of its root operation.

It walks the ``.xplane.pb`` with ``trace_scope_time``'s wire-format
functions (where a scope is found, and why not through
``jax.profiler.ProfileData``, is told there) and keeps, once a run, every
operation's whole ``tf_op``.

Parameters: ``outer``, ``inner``, ``module`` (part of the step program's
name; of several programs the one whose mean is longest is the top rung).
No trace, a program that names no such scope (the parent of the PR that
added it, or another embedder), or no whole run of the step in the window:
nothing is returned, never 0.
"""

import re

from benchmark import trace_reduce
from benchmark.readers import trace_scope_time
from benchmark.readers.trace_idle_under import overlap_ns


def op_tf_ops(path):
    """{operation name: its ``tf_op``} over the event metadata of the
    device planes."""
    fields, map_value = trace_scope_time._fields, trace_scope_time._map_value
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    found = {}
    for number, plane in fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in fields(plane):
            if field == 2:
                name = bytes(value).decode("utf-8", "replace")
            elif field == 4:
                events.append(map_value(value))
            elif field == 5:
                meta = dict(fields(map_value(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for event in events:
            op, text = None, None
            for field, value in fields(event):
                if field == 2:
                    op = bytes(value).decode("utf-8", "replace")
                elif field == 5 and text is None:
                    stat = dict(fields(value))
                    if stat_names.get(stat.get(1)) != trace_scope_time.SCOPE_STAT:
                        continue
                    text = (bytes(stat[5]).decode("utf-8", "replace") if 5 in stat
                            else stat_names.get(stat.get(7), ""))
            if op and text:
                found[op] = text
    return found


def inner_ops(ctx, outer, inner):
    """{plane: [(start_ns, end_ns)]} of the device operations under
    ``inner`` inside ``outer``; None without a trace file."""
    trace = ctx.get("trace")
    if "op_tf_ops" not in ctx:
        if not trace or not trace.get("file"):
            return None
        ctx["op_tf_ops"] = op_tf_ops(trace["file"])
    word = re.compile(rf"\b{re.escape(inner)}\b")
    wanted = set()
    for op, text in ctx["op_tf_ops"].items():
        first = trace_scope_time.SCOPE.search(text)
        if first and first.group(1) == outer and word.search(text):
            wanted.add(op)
    return {plane: [(start, start + dur) for name, start, dur in events
                    if name in wanted]
            for plane, events in trace_reduce.op_events(trace).items()}


def read(params, ctx):
    trace = ctx.get("trace")
    found = inner_ops(ctx, params["outer"], params["inner"])
    if not trace or not found:
        return None
    runs = trace_scope_time.top_rung_runs(
        trace, params["module"], ctx["trace_lo"], ctx["trace_hi"])
    times, ops = [], 0
    for plane, spans in runs.items():
        under = trace_reduce.union(found.get(plane, []))
        if under:
            ops += len(found[plane])
            times += [overlap_ns(under, [run]) for run in spans]
    if not times:
        return None
    ctx.setdefault("notes", {}).setdefault("inner_scope_runs", {})[
        params["outer"] + "/" + params["inner"]] = {"runs": len(times), "ops": ops}
    return sum(times) / len(times) / 1e6

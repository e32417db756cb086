"""Share of the traced window in which no operation ran on the device
WHILE the host was under one of the named annotations, in percent.

The device's idle intervals inside [trace_lo, trace_hi) are the gaps of
the union of its operations' intervals, as ``trace_idle_share`` takes
them; the host's annotations are the ``jax.profiler.TraceAnnotation``
events the program writes into the same trace (``ocvf:<stage>``, one per
span of ``utils/tracing.Tracer.span``), so both lie on the profiler's
clock and no offset is estimated.

Parameters: ``under`` (annotation names): idle time inside the union of
their intervals. Or ``not_under``: idle time under none of them. Metrics
over disjoint ``under`` sets plus one ``not_under`` over all of them add
up to the device's idle share exactly. Averaged over the chips that ran
anything, as the idle share is. No trace, or a trace that holds none of
the program's annotations at all: nothing is returned, never 0. (A trace
that holds them, but none of the named ones, reads 0 under them.)
"""

from benchmark import trace_reduce

#: what the program's annotations are named by (utils/tracing.py)
PREFIX = "ocvf:"


def gaps(busy, lo, hi):
    """The intervals of [lo, hi) outside ``busy`` (sorted, merged)."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap_ns(a, b):
    """Total length of the intersection of two sorted, merged lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share(op_events, annotations, names, lo, hi, inverse=False):
    """``op_events``: {plane: [(name, start, dur)]}; ``annotations``:
    {name: [(start, end)]}. Percent of [lo, hi), averaged over the
    planes that ran anything, idle under ``names`` (or, ``inverse``,
    idle under none of them); None when no plane ran anything."""
    cover = trace_reduce.union([iv for name in names
                                for iv in annotations.get(name, ())])
    shares = []
    for events in op_events.values():
        busy = trace_reduce.union(
            [(s, s + d) for _n, s, d in trace_reduce.clip(events, lo, hi)])
        if not busy:
            continue
        idle = gaps(busy, lo, hi)
        under = overlap_ns(idle, cover)
        if inverse:
            under = sum(e - s for s, e in idle) - under
        shares.append(100.0 * under / (hi - lo))
    return sum(shares) / len(shares) if shares else None


def load_annotations(path):
    """{name: [(start_ns, end_ns)]} of every event named ``ocvf:*`` on
    any plane of the trace file (they lie on the host's planes)."""
    from jax.profiler import ProfileData

    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(PREFIX):
                    start = int(event.start_ns)
                    found.setdefault(event.name, []).append(
                        (start, start + int(event.duration_ns)))
    return found


def read(params, ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("file"):
        return None
    if "host_annotations" not in ctx:  # parsed once a run, for every metric
        ctx["host_annotations"] = load_annotations(trace["file"])
    annotations = ctx["host_annotations"]
    if not annotations:
        return None
    ops = trace_reduce.op_events(trace)
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    notes = ctx.setdefault("notes", {})
    if "idle_under" not in notes:
        # For the reader of the run's detail file: the idle share under
        # each annotation the trace holds, one by one (those of different
        # threads overlap, so these do not add up).
        notes["idle_under"] = {
            name: idle_share(ops, annotations, [name], lo, hi)
            for name in sorted(annotations)}
    inverse = "not_under" in params
    return idle_share(ops, annotations,
                      params["not_under"] if inverse else params["under"],
                      lo, hi, inverse=inverse)

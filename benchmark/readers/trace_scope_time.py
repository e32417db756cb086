"""Device time a top-rung step spends under one ``jax.named_scope`` of the
program, in milliseconds: the union of the intervals of the device
operations that carry the scope, inside each run of the step's program on
the "XLA Modules" line, mean over the runs that lie whole in the window.

Where the scope is (found on the chip, PERF.md): not in an operation's
name, which is its HLO text, and in no stat of the event, but in the
``tf_op`` stat of the event's METADATA in the trace file
(``jit(packed_step)/ocvf_embed/IResNet/...``), which
``jax.profiler.ProfileData`` does not hand out. So this reader opens the
``.xplane.pb`` itself, once a run, and walks the protobuf wire format just
far enough to map every operation's name to the first ``ocvf_<stage>`` in
its ``tf_op`` (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
.stat_metadata = 5; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id
= 1, .str_value = 5, .ref_value = 7; XStatMetadata.name = 2). The events
themselves are the ones ``trace_reduce.load`` keeps. A fusion carries the
scope of its root operation.

Parameters: ``scope`` (the scope's name), ``module`` (part of the step
program's name; of several programs, one per rung, the one whose mean is
longest is the top rung, as ``trace_module_time`` takes it). No trace, a
program that names no scope (the parent of the PR that added them), or no
whole run of the step in the window: nothing is returned, never 0.
"""

import re
from collections import defaultdict

from benchmark import trace_reduce
from benchmark.readers.trace_idle_under import overlap_ns

SCOPE = re.compile(r"\b(ocvf_[a-z0-9]+)\b")
SCOPE_STAT = "tf_op"


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width ones skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            at += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _map_value(entry):
    """The value (field 2) of one entry of a protobuf map."""
    return next((v for number, v in _fields(entry) if number == 2), None)


def op_scopes(path):
    """{operation name: scope} over the event metadata of the device
    planes: the first ``ocvf_<stage>`` in each operation's ``tf_op``."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    found = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode("utf-8", "replace")
            elif field == 4:
                events.append(_map_value(value))
            elif field == 5:
                meta = dict(_fields(_map_value(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for event in events:
            op, scope = None, None
            for field, value in _fields(event):
                if field == 2:
                    op = bytes(value).decode("utf-8", "replace")
                elif field == 5 and scope is None:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    text = (bytes(stat[5]).decode("utf-8", "replace") if 5 in stat
                            else stat_names.get(stat.get(7), ""))
                    hit = SCOPE.search(text)
                    scope = hit.group(1) if hit else None
            if op and scope:
                found[op] = scope
    return found


def load_scoped_ops(trace):
    """{plane: {scope: [(start_ns, end_ns)]}} over the device operations
    ``trace`` holds, by the scope the trace file gives each one's name."""
    scopes = op_scopes(trace["file"])
    found = {}
    for plane, events in trace_reduce.op_events(trace).items():
        under = found.setdefault(plane, {})
        for name, start, dur in events:
            if name in scopes:
                under.setdefault(scopes[name], []).append((start, start + dur))
    return found


def scoped_ops(ctx):
    """The run's scoped operations, parsed once for every metric; None
    without a trace file."""
    trace = ctx.get("trace")
    if "scoped_ops" not in ctx:
        if not trace or not trace.get("file"):
            return None
        ctx["scoped_ops"] = load_scoped_ops(trace)
        ctx.setdefault("notes", {})["scoped_ops"] = {
            scope: len(spans) for under in ctx["scoped_ops"].values()
            for scope, spans in under.items()}
    return ctx["scoped_ops"]


def seconds_under(scoped, scope, lo, hi):
    """Seconds of [lo, hi) in which an operation of ``scope`` ran, averaged
    over the planes that hold the scope; None where none does."""
    per_plane = []
    for scopes in scoped.values():
        spans = trace_reduce.union(scopes.get(scope, []))
        if spans:
            per_plane.append(overlap_ns(spans, [(lo, hi)]) / 1e9)
    return sum(per_plane) / len(per_plane) if per_plane else None


def top_rung_runs(trace, match, lo, hi):
    """{plane: [(start, end)]} of the whole runs in [lo, hi) of the
    program named like ``match`` whose mean duration is longest."""
    groups = defaultdict(lambda: defaultdict(list))
    for plane, lines in trace["devices"].items():
        for name, start, dur in lines.get(trace_reduce.MODULES_LINE, []):
            if match in name and start >= lo and start + dur <= hi:
                groups[name][plane].append((start, start + dur))
    if not groups:
        return {}
    mean = {name: sum(e - s for runs in planes.values() for s, e in runs)
            / sum(len(runs) for runs in planes.values())
            for name, planes in groups.items()}
    return dict(groups[max(mean, key=mean.get)])


def read(params, ctx):
    trace = ctx.get("trace")
    scoped = scoped_ops(ctx)
    if not trace or not scoped:
        return None
    runs = top_rung_runs(trace, params["module"], ctx["trace_lo"], ctx["trace_hi"])
    times = []
    for plane, spans in runs.items():
        under = trace_reduce.union(scoped.get(plane, {}).get(params["scope"], []))
        if under:
            times += [overlap_ns(under, [run]) for run in spans]
    if not times:
        return None
    ctx.setdefault("notes", {}).setdefault("scope_runs", {})[params["scope"]] = len(times)
    return sum(times) / len(times) / 1e6

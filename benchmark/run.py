"""The benchmark's command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Everything that belongs to one cell is found by name from
``BENCHMARK.json``: ``benchmark/configs/<config>.json`` (with its plain
reference beside it), ``benchmark/traffic/<traffic>.json``,
``benchmark/layer_metrics/<metric>.json`` and the reader it names under
``benchmark/readers/``. No name of a cell, a configuration, a mix or a
metric appears in this file.

The last line on standard output is the result; what else a run found
(set-up split, batches closed by size and by deadline, served frames per
5 s, stalls) goes to standard error and to a file under ``--out``
(default ``.bench_work/out``).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a traced run measures this long at most (PERF.md, "Traced runs")
TRACE_SECONDS = 10.0


def say(message: str) -> None:
    print(f"[bench {time.perf_counter() - PROCESS_START:7.1f}s] {message}",
          file=sys.stderr, flush=True)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)

    def listed(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
            "per_layer": [m for m in bench["per_layer"] if listed(m)]}


def device_gate(chips: int) -> dict:
    """First JAX call of the process: every device a TPU, and as many as
    the cell asks for. Nothing makes this pass on a CPU."""
    import jax

    devices = jax.devices()
    if any(d.platform != "tpu" for d in devices) or len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}. "
            f"Nothing was run.")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_bytes(chips: int, key: str = "peak_bytes_in_use") -> int:
    """``key`` of JAX's memory statistics, on the fullest chip."""
    import jax

    return int(max((d.memory_stats() or {}).get(key, 0)
                   for d in jax.devices()[:chips]))


def read_layer_metrics(cellinfo: dict, ctx: dict) -> dict:
    out = {}
    for metric in cellinfo["per_layer"]:
        with open(os.path.join(HERE, "layer_metrics", metric["name"] + ".json")) as fh:
            params = json.load(fh)
        reader = importlib.import_module(f"benchmark.readers.{params['reader']}")
        value = reader.read(params, ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def host_spans_on_trace_clock(spans, offset_ns: int):
    return [(s["stage"], int(s["t0"] * 1e9) + offset_ns,
             int((s["t0"] + s["dur"]) * 1e9) + offset_ns) for s in spans]


def run(argv=None, gate=device_gate) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_work", "out"))
    args = ap.parse_args(argv)

    cellinfo = load_cell(args.workload)
    cell, config = cellinfo["cell"], cellinfo["config"]
    device = gate(int(cell["chips"]))
    t_import = time.perf_counter()

    from benchmark import check, peaks, trace_reduce, traffic_gen, window
    from opencv_facerecognizer_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    peak_table = peaks.peaks_for(device["kind"])
    say(f"device: platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']}; compile cache: {cache_dir}")
    compiles = window.CompileCounter()
    split = {"to_gate": t_import - PROCESS_START}

    t0 = time.perf_counter()
    traffic = traffic_gen.Traffic(cellinfo["traffic"], args.seed,
                                  tuple(config["frame_size"]))
    split["traffic"] = time.perf_counter() - t0
    stack_module = importlib.import_module(f"benchmark.stacks.{config['stack']}")
    stack = stack_module.build(config, traffic, args.seed, say,
                               trace=bool(args.trace))
    split.update(stack.split)
    resident = memory_bytes(int(cell["chips"]), "bytes_in_use")

    trace_dir = None
    seconds = args.seconds
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_work", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, TRACE_SECONDS)
    win = window.run_window(stack, traffic, seconds, args.seed, say, compiles,
                            trace_dir=trace_dir, process_start=PROCESS_START)
    split["warm_period"] = traffic.params["warm_seconds"]
    peak = memory_bytes(int(cell["chips"]))

    spans = []
    if stack.tracer is not None:
        from opencv_facerecognizer_tpu.utils.tracing import BATCH_TOPIC

        spans = [s for s in stack.tracer.snapshot(BATCH_TOPIC)
                 if win["t0_mono"] <= s["t0"] + s["dur"] <= win["t1_mono"]]

    # The program's state goes, its gallery with it: the reference gets the
    # rows drawn again from the seed, nothing that the program has stored.
    nets_dir, label_offset = stack.nets["dir"], stack.label_offset
    enrol_images, enrol_labels = stack.enrol_images, stack.enrol_labels
    nets_info = stack.nets
    top_rung = stack.top_rung_frames()
    stack.close()
    del stack
    gc.collect()  # the service, its hooks and the stack refer to one another
    rows = stack_module.reference_rows(config, args.seed)

    judged = window.numbers_compared(
        config, traffic, args.seed, win, nets_dir, rows, enrol_images,
        enrol_labels, label_offset)
    correct, table = check.verdict(judged["numbers"], window.load_limits(config))
    correct = correct and win["completed"] > 0
    del rows

    device_out = {**device, "memory_peak_bytes": peak}
    breakdown = None
    notes = {}
    if args.trace:
        trace = trace_reduce.load(trace_dir)
        ctx = {"counters": win["counters"], "spans": spans, "trace": trace,
               "config": config, "peaks": peak_table, "top_rung": top_rung,
               "notes": notes}
        if trace is not None and trace["devices"]:
            if trace["sync_ns"] is not None:
                offset = trace["sync_ns"] - win["sync_mono_ns"]
                lo = int(win["t0_mono"] * 1e9) + offset
                hi = int(win["t1_mono"] * 1e9) + offset
            else:
                offset = None
                lo, hi = trace_reduce.window_of(trace)
            ctx["trace_lo"], ctx["trace_hi"] = lo, hi
            busy = trace_reduce.busy_seconds(trace, lo, hi)
            if busy is None:
                raise SystemExit("benchmark: the trace holds no device operation")
            device_out["busy_s"] = busy
            device_out["window_s"] = (hi - lo) / 1e9
            host = (host_spans_on_trace_clock(spans, offset)
                    if offset is not None else [])
            breakdown = {"device_ops": trace_reduce.top_ops(trace, lo, hi),
                         "idle_gaps": trace_reduce.idle_gaps(trace, lo, hi, host)}
            notes["trace_shape"] = trace["shape"]
            notes["clock_sync"] = offset is not None
        else:
            raise SystemExit("benchmark: the profiler wrote no device trace")
        metrics = read_layer_metrics(cellinfo, ctx)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        measured = {"served_fps": win["served_fps"], "setup_s": win["setup_s"]}
        metrics = {m["name"]: {"value": float(measured[m["name"]]), "unit": m["unit"]}
                   for m in cellinfo["end_to_end"]}

    detail = {
        "workload": args.workload, "seed": args.seed, "seconds": seconds,
        "trace": args.trace, "device": device_out,
        "memory_resident_after_setup_bytes": resident, "compile_cache": cache_dir,
        "nets": nets_info, "setup_split_s": split, "window_s": win["window_s"],
        "counters_window": {k: win["counters"][k] for k in sorted(win["counters"])},
        "batches": win["batches"], "drops": win["drops"], "ledger_end": win["ledger"],
        "queue_target": win["queue_target"], "queue_max_seen": win["queue_max_seen"],
        "served_fps": win["served_fps"], "served_fps_by_5s": win["served_fps_by_5s"],
        "stalls": win["stalls"], "late_refills": win["late_refills"],
        "census_window": traffic.census(win["first_index"], win["next_index"]),
        "profiler_s": {k: win[k] for k in ("profiler_start_s", "profiler_stop_s")
                       if k in win},
        "judged": {k: v for k, v in judged.items() if k != "numbers"},
        "compared": table, "notes": notes, "metrics": metrics,
        "breakdown": breakdown,
    }
    os.makedirs(args.out, exist_ok=True)
    name = f"{args.workload}.seed{args.seed}.trace{args.trace}.json"
    with open(os.path.join(args.out, name), "w") as fh:
        json.dump(detail, fh, indent=1, default=str)
    say("set-up split (s): " + json.dumps({k: round(v, 2) for k, v in split.items()})
        + f"; device memory held after set-up {resident} bytes, peak {peak}")
    say(f"batches in window: {win['batches']}; served frames/s per 5 s: "
        f"{[round(v, 1) for v in win['served_fps_by_5s']]}; stalls over 50 ms: "
        f"{len(win['stalls'])}; refills later than 20 ms: {len(win['late_refills'])}; "
        f"reference {judged['reference_s']:.1f} s over {judged['sampled']}")

    result = {"correct": bool(correct), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = table
    for key, (value, limit) in table.items():
        print(f"compared {key}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())

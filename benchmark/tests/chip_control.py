"""The controls of "How correct is decided", on the chip at the cell's own
size: one process builds the cell's stack once and then, for each seed
given, drives a short window at the cell's own load with that seed's
traffic and reads, over the same sample of frames:

    sound           the program's results held against the reference
    control         the reference one precision step lower in the program's
                    place (fp8 convolutions and head, int8 rows and queries)
    control_gallery the same with int8 rows and queries alone
    plant_<name>    the program's results with one guarantee broken
                    (``benchmark/tests/plants.py``), on the first
                    ``PLANT_SEEDS`` seeds

The limits in ``benchmark/configs/<config>.limits.json`` are set between
the sound readings and the others'; every per-face reading is written out,
so that the ``far`` thresholds can be set from the same call.

    chiprun -- python3 benchmark/tests/chip_control.py <name> <workload> <seconds> <seed> [<seed> ...]

The gallery and the enrolled subjects are those of the first seed; later
seeds change the frames (identities, places, pixels, stream phases). The
reference matches against rows drawn again from that seed, as a run's does;
the record says whether they equal the rows the program serves. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PLANT_SEEDS = 12


def main(argv) -> int:
    from benchmark import run

    name, workload, seconds = argv[0], argv[1], float(argv[2])
    seeds = [int(s) for s in argv[3:]]
    cellinfo = run.load_cell(workload)
    config = cellinfo["config"]
    chips = int(cellinfo["cell"]["chips"])
    run.device_gate(chips)

    import jax
    import jax.numpy as jnp

    from benchmark import check, traffic_gen, window
    from benchmark.tests import plants
    from opencv_facerecognizer_tpu.utils import compile_cache

    compile_cache.enable()
    compiles = window.CompileCounter()
    traffic = traffic_gen.Traffic(cellinfo["traffic"], seeds[0],
                                  tuple(config["frame_size"]))
    stack_module = importlib.import_module(f"benchmark.stacks.{config['stack']}")
    stack = stack_module.build(config, traffic, seeds[0], run.say)
    resident = run.memory_bytes(chips, "bytes_in_use")
    rows = stack_module.reference_rows(config, seeds[0])
    head = stack.enrolled_rows
    same = bool(jax.jit(lambda a, b: jnp.all(  # one fused pass, no copy of either
        (a == b) | (jnp.arange(a.shape[0])[:, None] < head)))(
            rows, stack.gallery.data.embeddings))
    run.say(f"rows drawn again equal the rows served past the {head} enrolled: {same}; "
            f"device memory held after set-up {resident} bytes")
    limits = window.load_limits(config)
    out_path = os.path.join(ROOT, "chiprun_out", name + ".jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    start_index = 0
    for n, seed in enumerate(seeds):
        if seed != seeds[0]:
            traffic = traffic_gen.Traffic(cellinfo["traffic"], seed,
                                          tuple(config["frame_size"]))
        tracker = getattr(stack.service, "tracker", None)
        if tracker is not None:
            tracker.flush_all()  # no track of the last seed's streams answers this one's
        win = window.run_window(stack, traffic, seconds, seed, run.say, compiles,
                                start_index=start_index)
        start_index = win["next_index"] + 4096
        record = {"workload": workload, "seed": seed, "seconds": seconds,
                  "served_fps": win["served_fps"], "batches": win["batches"],
                  "failed": win["failed"], "rows_equal_served": same,
                  "resident_bytes": resident}
        variants = [("sound", {}), ("control", {"control": "nets+gallery"}),
                    ("control_gallery", {"control": "gallery"})]
        if n < PLANT_SEEDS:
            variants += [("plant_" + k, {"plant": v}) for k, v in plants.PLANTS.items()]
        for key, how in variants:
            t0 = time.perf_counter()
            judged = window.numbers_compared(
                config, traffic, seed, win, stack.nets["dir"], rows,
                stack.enrol_images, stack.enrol_labels, stack.label_offset, **how)
            ok, _table = check.verdict(judged["numbers"], limits)
            record[key] = {"correct_by_current_limits": ok,
                           "numbers": judged["numbers"],
                           "seen": {k: v for k, v in judged.items()
                                    if k not in ("numbers", "sampled")},
                           "sampled": judged["sampled"],
                           "seconds": round(time.perf_counter() - t0, 2)}
        with open(out_path, "a") as fh:
            fh.write(json.dumps(record) + "\n")
        short = {k: {"ok": v["correct_by_current_limits"],
                     **{m: round(x, 4) for m, x in v["numbers"].items()}}
                 for k, v in record.items() if isinstance(v, dict) and "numbers" in v}
        print(json.dumps({"seed": seed, "served_fps": round(win["served_fps"], 1),
                          **short}), flush=True)
    stack.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

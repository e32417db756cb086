"""What a sharded watchlist adds to "How correct is decided", read on the
chips at the cell's own size: does the comparison that decides ``correct``
see a shard that is lost, and an exchange between the chips that is left
out? The watchlist's planted rows (``stacks/recognize_sharded.plant``) put
some face's best row on every chip in every frame, so it has to.

    chiprun --chips 4 -- python3 benchmark/tests/chip_shards.py <name> <workload> <seconds> <seed> <variant> [<variant> ...]

One process builds the cell's stack once and drives one short window at
the cell's own load for each variant, in the order given:

    sound        the program as it is. Over the same window, as
                 ``chip_control.py`` reads them: the reference one precision
                 step lower in the program's place (``control``,
                 ``control_gallery``) and the program's results with one
                 guarantee broken (``plant_<name>``, ``plants.py``)
    lost:<k>     shard k's ``valid`` cleared by hand through the program's
                 own bulk install, as if that chip's fill had never landed
                 (put back after the window)
    no_exchange  ``all_gather`` left out of the merge, so every chip merges
                 its own shard's candidates alone and the first chip's
                 answer is read. The step is traced without it, so this is
                 the only variant of its process

Every window's results go through ``window.numbers_compared`` and
``check.verdict`` against the configuration's limits, as a run's do, and
besides each published face's served row is held against the reference's
best row over the WHOLE watchlist, by the shard of that row. ``sound`` has
to come out correct and every other variant not: the exit code says
whether they did. Appends one record a window to
``chiprun_out/<name>.jsonl``. The benchmark's own runs never run this.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def served_against_best(config, traffic, seed, win, stack, rows, reference):
    """{"faces", "agree", "by_shard": {shard: [faces, agree]}} over the
    window's sample of full results."""
    import numpy as np

    from benchmark import check

    collector = win["collector"]
    results = {s: m for s, m in collector.kept.items()
               if win["t0"] <= collector.kept_at[s] <= win["t1"]}
    sample = check.draw_sample(seed, results, {
        check.EXIT_FULL: int(config["check"]["sample_full"])})[check.EXIT_FULL]
    k = int(config["max_faces"])
    pixels = np.stack([traffic.frame_of(s) for s in sample])
    boxes = np.zeros((len(sample), k, 4), np.float32)
    served = np.full((len(sample), k), -2, np.int64)  # -2: no face in the slot
    offset, n_head = stack.label_offset, len(stack.enrol_labels)
    for n, s in enumerate(sample):
        for j, face in enumerate(results[s]["faces"][:k]):
            x0, y0, x1, y1 = face["box"]
            boxes[n, j] = (y0, x0, y1, x1)
            served[n, j] = int(face["label"])
    emb = reference.embed(pixels, boxes).reshape(len(sample) * k, -1)
    head = reference.embed_images(stack.enrol_images)
    block = int(config["gallery"].get("reference_block_rows",
                                      config["gallery"]["block_rows"]))
    _best, best_idx, _at = reference.match(emb, rows, n_head, head, block)
    best_idx = best_idx.reshape(len(sample), k)
    shard_rows = int(config["gallery"]["rows"])
    out = {"faces": 0, "agree": 0, "unknown": 0, "by_shard": {}}
    for n in range(len(sample)):
        for j in range(k):
            label = served[n, j]
            if label == -2:
                continue
            best = int(best_idx[n, j])
            if best < n_head:  # an enrolled row: its subject's label is served
                agree = label == int(stack.enrol_labels[best])
            else:
                agree = label == offset + best
            shard = str(best // shard_rows)
            cell = out["by_shard"].setdefault(shard, [0, 0])
            cell[0] += 1
            cell[1] += int(agree)
            out["faces"] += 1
            out["agree"] += int(agree)
            out["unknown"] += int(label < 0)
    return out


def without_shard(gallery, shard_rows: int, lost: int) -> None:
    """Installs the served rows again with shard ``lost`` not valid."""
    import jax
    import jax.numpy as jnp

    data = gallery.data
    valid = jax.jit(
        lambda v: v & (jnp.arange(v.shape[0]) // shard_rows != lost),
        out_shardings=data.valid.sharding)(data.valid)
    gallery.install_device_rows(data.embeddings, data.labels, valid, data.size)


def main(argv) -> int:
    from benchmark import run

    name, workload, seconds, seed = argv[0], argv[1], float(argv[2]), int(argv[3])
    variants = argv[4:]
    if "no_exchange" in variants and variants != ["no_exchange"]:
        raise SystemExit("no_exchange is the only variant of its process")
    cellinfo = run.load_cell(workload)
    config = cellinfo["config"]
    run.device_gate(int(cellinfo["cell"]["chips"]))

    import jax

    from benchmark import check, traffic_gen, window
    from benchmark.tests import plants
    from opencv_facerecognizer_tpu.utils import compile_cache

    if variants == ["no_exchange"]:
        jax.lax.all_gather = lambda x, *args, **kwargs: x
    compile_cache.enable()
    compiles = window.CompileCounter()
    traffic = traffic_gen.Traffic(cellinfo["traffic"], seed,
                                  tuple(config["frame_size"]))
    stack_module = importlib.import_module(f"benchmark.stacks.{config['stack']}")
    stack = stack_module.build(config, traffic, seed, run.say)
    rows = stack_module.reference_rows(config, seed)
    reference = window.load_reference(config).Reference(
        stack.nets["dir"], tuple(config["face_size"]))
    limits = window.load_limits(config)

    def judged(win, **how):
        got = window.numbers_compared(
            config, traffic, seed, win, stack.nets["dir"], rows,
            stack.enrol_images, stack.enrol_labels, stack.label_offset, **how)
        ok, table = check.verdict(got["numbers"], limits)
        return {"correct": bool(ok and win["completed"] > 0), "compared": table}

    out_path = os.path.join(ROOT, "chiprun_out", name + ".jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    start_index, as_expected = 0, True
    for variant in variants:
        whole = stack.gallery.data
        if variant.startswith("lost:"):
            without_shard(stack.gallery, int(config["gallery"]["rows"]),
                          int(variant.split(":")[1]))
        win = window.run_window(stack, traffic, seconds, seed, run.say, compiles,
                                start_index=start_index)
        start_index = win["next_index"] + 4096
        record = {"workload": workload, "seed": seed, "variant": variant,
                  "served_fps": win["served_fps"], "failed": win["failed"],
                  "window_compiles": win["counters"].get("bench_backend_compiles"),
                  **judged(win),
                  **served_against_best(config, traffic, seed, win, stack, rows,
                                        reference)}
        as_expected &= record["correct"] == (variant == "sound")
        if variant == "sound":
            others = {"control": {"control": "nets+gallery"},
                      "control_gallery": {"control": "gallery"},
                      **{"plant_" + k: {"plant": v} for k, v in plants.PLANTS.items()}}
            record["in_its_place"] = {k: judged(win, **how) for k, how in others.items()}
        if variant.startswith("lost:"):
            stack.gallery.install_device_rows(whole.embeddings, whole.labels,
                                              whole.valid, whole.size)
        with open(out_path, "a") as fh:
            fh.write(json.dumps(record) + "\n")
        print(json.dumps(record), flush=True)
    stack.close()
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

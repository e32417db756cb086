"""Runs cells of the benchmark one after another on the machine it is
started on, each in a process of its own (this one never touches JAX), and
appends every result line to ``chiprun_out/<name>.jsonl``:

    chiprun -- python3 benchmark/tests/chip_runs.py <name> <workload>,<seed>,<seconds>,<trace> ...

The runs' detail files go to ``chiprun_out/<name>/``; nets trained during
the call are copied to ``chiprun_out/nets/`` so that they can be committed.
A spec may be written ``archive:<spec>``: it then runs from a copy of the
committed files unpacked under ``.chip_scratch/`` by the caller.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    name, specs = argv[0], argv[1:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(os.path.join(out_dir, name), exist_ok=True)
    worst = 0
    for spec in specs:
        root = ROOT
        if spec.startswith("archive:"):
            spec, root = spec[len("archive:"):], os.path.join(ROOT, ".chip_scratch", "co")
        workload, seed, seconds, trace = spec.split(",")
        cmd = [sys.executable, "benchmark/run.py", "--workload", workload,
               "--seed", seed, "--seconds", seconds, "--trace", trace,
               "--out", os.path.join(out_dir, name)]
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        record = {"spec": spec, "root": os.path.relpath(root, ROOT), "rc": proc.returncode,
                  "wall_s": round(wall, 1), "result": result,
                  "stderr_tail": proc.stderr[-3000:]}
        with open(os.path.join(out_dir, name + ".jsonl"), "a") as fh:
            fh.write(json.dumps(record) + "\n")
        short = {k: (v["value"] if isinstance(v, dict) else v)
                 for k, v in ((result or {}).get("metrics") or {}).items()}
        print(f"{spec}: rc={proc.returncode} wall={wall:.0f}s correct="
              f"{(result or {}).get('correct')} failed={(result or {}).get('failed')} {short}",
              flush=True)
        if proc.returncode != 0 or result is None:
            print(proc.stderr[-2500:], flush=True)
            worst = max(worst, proc.returncode or 1)
    nets = os.path.join(ROOT, ".bench_work", "nets")
    if os.path.isdir(nets):
        shutil.copytree(nets, os.path.join(out_dir, "nets"), dirs_exist_ok=True)
    return worst


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

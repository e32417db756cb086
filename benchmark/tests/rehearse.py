"""Helpers of the rehearsals: a temporary copy of the benchmark that gains a
throw-away configuration, traffic mix and layer metric as NEW files (no file
that is there is edited), and a run of one cell of it on the CPU with the
harness's look for a chip skipped. Rehearsal only: the line such a run
prints names the CPU as its device and is never a device measurement.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
FIXTURES = os.path.join(HERE, "fixtures")

DRIVER = r"""
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(1, {repo!r})
import jax
from benchmark import run

def gate(chips):
    d = jax.devices()[0]
    return {{"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}}

from benchmark import peaks
peaks.DEVICE_PEAKS.setdefault(jax.devices()[0].device_kind,
                              {{"bf16_tflops": 1.0, "hbm_gb_per_s": 1.0}})
{patch}
sys.exit(run.run({argv!r}, gate=gate))
"""


#: lets a traced rehearsal read the CPU's own plane as if it were a chip's
CPU_TRACE_PATCH = (
    "from benchmark import trace_reduce\n"
    "trace_reduce.DEVICE_PREFIX = '/host:CPU'\n")


def make_copy(tmp: str) -> str:
    """Copies BENCHMARK.json and benchmark/ into ``tmp`` and adds the
    fixtures as new files and new entries; returns the copy's root."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), root): os.path.getmtime(os.path.join(d, f))
              for d, _s, fs in os.walk(root) for f in fs}
    copies = {"tiny.json": "configs/tiny.json",
              "tiny.limits.json": "configs/tiny.limits.json",
              "trickle.json": "traffic/trickle.json",
              "still.json": "traffic/still.json",
              "settled_share.rehearsal.json": "layer_metrics/settled_share.rehearsal.json"}
    for src, dst in copies.items():
        target = os.path.join(root, "benchmark", dst)
        assert not os.path.exists(target), f"{dst} would overwrite a file"
        shutil.copy(os.path.join(FIXTURES, src), target)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tiny", "source": "rehearsal",
                             "file": "benchmark/configs/tiny.json",
                             "reduced": [], "why": "rehearsal"})
    theirs = [w["name"] for w in bench["workloads"]]
    for traffic in ("trickle", "still"):
        bench["workloads"].append({"name": "tiny." + traffic, "config": "tiny",
                                   "traffic": traffic, "chips": 1, "why": "rehearsal"})
    for metric in bench["per_layer"]:
        metric.setdefault("workloads", list(theirs))
        metric["workloads"] += ["tiny.trickle", "tiny.still"]
    bench["per_layer"].append({"name": "settled_share.rehearsal", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "service loop", "moves": "served_fps",
                               "workloads": ["tiny.trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    after = {p: os.path.getmtime(os.path.join(root, p)) for p in before}
    assert before == after, "an existing file of the benchmark was edited"
    return root


def run_cell(root: str, argv: list, patch: str = "", timeout: float = 900.0):
    """Runs ``benchmark/run.py``'s ``run`` of the copy at ``root`` in a
    process of its own; returns (return code, result line or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    code = DRIVER.format(root=root, repo=REPO, argv=argv, patch=patch)
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return proc.returncode, result, proc.stderr


if __name__ == "__main__":
    import tempfile

    keep = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp(prefix="bench_rehearse_")
    root = os.path.join(keep, "checkout")
    if not os.path.isdir(root):
        root = make_copy(keep)
    trace = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    rc, result, err = run_cell(root, ["--workload", "tiny.trickle", "--seed", "2999123456",
                                      "--seconds", "3", "--trace", str(trace)],
                               patch=CPU_TRACE_PATCH)
    print(err[-6000:])
    print(rc, json.dumps(result)[:3000])

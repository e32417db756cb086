"""Profiler-trace evidence for the fused serving step (VERDICT r3 item #2):
capture a jax.profiler trace of the batch-32 fused graph, parse it with
jax.profiler.ProfileData (no TensorBoard needed), and land a trace_summary
— top device ops by self time and the device busy/idle fraction — in
BENCH_DETAIL.json. This is the "why is the chip 87% idle" artifact the
stage attribution (which explains *where the milliseconds* go) cannot
answer on its own.

Run:  PYTHONPATH=. python scripts/trace_summary.py [--steps 64] [--batch 32]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from collections import defaultdict

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def build_pipeline(batch, h, w, max_faces, dim, tiny=False):
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.models.detector import CNNFaceDetector
    from opencv_facerecognizer_tpu.models.embedder import (
        FaceEmbedNet, init_embedder,
    )
    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline
    from opencv_facerecognizer_tpu.utils.dataset import make_synthetic_scenes

    if tiny:
        det = CNNFaceDetector(features=(8, 8), head_features=8,
                              max_faces=max_faces, score_threshold=0.0,
                              space_to_depth=2)
        import jax as _jax
        det.load_params(det.net.init(_jax.random.PRNGKey(0),
                                     jnp.zeros((1, h, w)))["params"])
        face = (32, 32)
        cap = 256
        scenes = make_synthetic_scenes(num_scenes=batch, scene_size=(h, w),
                                       max_faces=max_faces, seed=7)[0]
        net = FaceEmbedNet(embed_dim=dim, stem_features=8,
                           stage_features=(8,), stage_blocks=(1,))
        emb_params = init_embedder(net, num_classes=4, input_shape=face,
                                   seed=0)["net"]
    else:
        # The SERVING-default pipeline, via the one shared constructor
        # (bench_serving.build_pipeline) so this artifact can never drift
        # from the config the serving benches measure.
        import bench_serving

        pipe, frame_pool = bench_serving.build_pipeline(
            frame_hw=(h, w), gallery_size=16384)
        frames = jnp.asarray(np.stack(
            [frame_pool[i % len(frame_pool)] for i in range(batch)]),
            jnp.float32)
        return pipe, frames
    rng = np.random.default_rng(0)
    # bf16 rows: the ocvf-recognize serving default (gallery_dtype A/B)
    gallery = ShardedGallery(capacity=cap, dim=dim, mesh=make_mesh(),
                             store_dtype=jnp.bfloat16)
    gallery.add(rng.normal(size=(cap, dim)).astype(np.float32),  # ocvf-lint: boundary=wal-before-mutate -- trace fixture: synthetic gallery, traces are the artifact, nothing durable
                rng.integers(0, 512, cap).astype(np.int32))
    pipe = RecognitionPipeline(det, net, emb_params, gallery,
                               face_size=face)
    frames = jnp.asarray(scenes[:batch], jnp.float32)
    return pipe, frames


def _line_self_times(events):
    """True per-op SELF time for one trace line: each event's duration minus
    the durations of events nested directly inside it. Summing raw
    durations would double-count nested events (a parent op enclosing its
    children on the same line), inflating top-op totals relative to the
    busy-fraction path, which unions intervals. Assumes proper nesting
    within a line, which xplane guarantees per-line."""
    self_ns = defaultdict(int)
    stack = []  # [end_ns, name, duration_ns, direct_child_ns]

    def _close(frame):
        end, name, dur, child_ns = frame
        self_ns[name] += max(dur - child_ns, 0)
        if stack:
            stack[-1][3] += dur  # charge full duration to direct parent

    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        dur = e.duration_ns or max(e.end_ns - e.start_ns, 0)
        while stack and stack[-1][0] <= e.start_ns:
            _close(stack.pop())
        stack.append([e.end_ns, e.name, dur, 0])
    while stack:
        _close(stack.pop())
    return self_ns


def summarize_xspace(trace_dir, top_n=20):
    """Parse the newest .xplane.pb under trace_dir into {planes, per-plane
    busy fraction, top ops}. Works purely through jax.profiler.ProfileData."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"error": f"no .xplane.pb produced under {trace_dir}"}
    data = ProfileData.from_file(paths[-1])
    out = {"xplane": os.path.relpath(paths[-1], trace_dir), "planes": []}
    for plane in data.planes:
        lines_summary = []
        plane_span_start, plane_span_end = None, None
        op_self_ns = defaultdict(int)
        total_event_ns = 0
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            start = min(e.start_ns for e in events)
            end = max(e.end_ns for e in events)
            plane_span_start = (start if plane_span_start is None
                                else min(plane_span_start, start))
            plane_span_end = (end if plane_span_end is None
                              else max(plane_span_end, end))
            # busy = union of event intervals on this line (events on one
            # line can nest; union avoids double-counting parents)
            ivals = sorted((e.start_ns, e.end_ns) for e in events)
            busy = 0
            cur_s, cur_e = ivals[0]
            for s, e in ivals[1:]:
                if s > cur_e:
                    busy += cur_e - cur_s
                    cur_s, cur_e = s, e
                else:
                    cur_e = max(cur_e, e)
            busy += cur_e - cur_s
            for name, ns in _line_self_times(events).items():
                op_self_ns[name] += ns
                total_event_ns += ns
            lines_summary.append({
                "line": line.name, "events": len(events),
                "busy_ms": round(busy / 1e6, 3),
                "span_ms": round((end - start) / 1e6, 3),
                "busy_fraction": round(busy / max(end - start, 1), 4),
            })
        top = sorted(op_self_ns.items(), key=lambda kv: -kv[1])[:top_n]
        out["planes"].append({
            "name": plane.name,
            "lines": lines_summary,
            "top_ops_ms": [
                {"op": k, "total_ms": round(v / 1e6, 3),
                 "share_of_events": round(v / max(total_event_ns, 1), 4)}
                for k, v in top
            ],
        })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--trace-dir", default="/tmp/ocvf_trace")
    ap.add_argument("--tiny", action="store_true",
                    help="small nets/gallery + few steps: smoke-tests the "
                         "capture+parse path on any backend (CPU included) "
                         "without writing BENCH_DETAIL.json")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    _log(f"device: {dev}")
    if args.tiny:
        pipe, frames = build_pipeline(4, 96, 96, 2, 32, tiny=True)
        args.steps = min(args.steps, 4)
    else:
        pipe, frames = build_pipeline(args.batch, 256, 256, 8, 128)
    # warm/compile OUTSIDE the trace
    _ = np.asarray(pipe.recognize_batch_packed(frames))
    t0 = time.perf_counter()
    with jax.profiler.trace(args.trace_dir):
        for _i in range(args.steps):
            out = pipe.recognize_batch_packed(frames)
        _ = np.asarray(out)  # one readback closes the chain
    wall_s = time.perf_counter() - t0
    _log(f"traced {args.steps} steps in {wall_s:.2f}s")

    summary = summarize_xspace(args.trace_dir)
    summary["steps"] = args.steps
    summary["batch"] = args.batch
    summary["wall_s_traced_region"] = round(wall_s, 3)
    summary["device"] = str(dev)
    summary["date"] = time.strftime("%Y-%m-%d")
    summary["note"] = (
        "jax.profiler trace of the steady-state fused step (compile outside "
        "the trace; steps dispatched back-to-back, ONE readback at the "
        "end). "
        "busy_fraction is per trace line (union of event intervals / line "
        "span); top_ops_ms aggregates TRUE self time by op name (each "
        "event's duration minus its direct children's), so nested events "
        "are not double-counted and totals are comparable to busy time."
    )

    if args.tiny:
        print(json.dumps(summary, indent=2)[:4000])
        return
    detail_path = os.path.join(REPO, "BENCH_DETAIL.json")
    try:
        detail = json.load(open(detail_path))
    except (OSError, json.JSONDecodeError):
        detail = {}
    # Batch 32 (the headline) keeps the long-standing top-level key;
    # other batch sizes land beside it instead of clobbering it.
    key = ("trace_summary" if args.batch == 32
           else f"trace_summary_b{args.batch}")
    detail[key] = summary
    from opencv_facerecognizer_tpu.utils.serialization import atomic_write_json

    atomic_write_json(detail_path, detail)
    _log(f"merged {key} into BENCH_DETAIL.json")
    print(json.dumps(summary, indent=2)[:4000])


if __name__ == "__main__":
    main()

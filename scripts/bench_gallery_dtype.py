"""A/B the gallery store dtype (f32 vs bf16 vs int8) at the 1M-row tier:
in-graph match cost (chained differencing, see bench.py) and upload wall
(device_put
+ the residency await the grow worker uses). f32 and bf16 compute
bf16 x bf16 -> f32 regardless of storage, so bf16 storage should halve
HBM traffic and upload bytes at identical math. The int8 arm measures the
IVF quantizer's storage format (``parallel.quantizer.quantize_rows``:
per-row scale, dequantized to bf16 in-graph before the same exact
kernel) — quarter the bytes of f32 with a measured, not assumed,
accuracy column (tie-aware top-1 agreement + max |sim diff| vs the f32
arm, the same comparator as the IVF recall gate).

Run:  PYTHONPATH=. python scripts/bench_gallery_dtype.py
Merges a "gallery_dtype" section into BENCH_DETAIL.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    import jax
    import jax.numpy as jnp

    from opencv_facerecognizer_tpu.parallel import ShardedGallery, make_mesh

    rows, dim, q_batch, k = 1_048_576, 256, 256, 1
    dev = jax.devices()[0]
    _log(f"device: {dev}; {rows} rows x {dim}")
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((rows, dim), dtype=np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    lab = rng.integers(0, 4096, rows).astype(np.int32)
    q = emb[:q_batch]

    result = {"rows": rows, "dim": dim, "q_batch": q_batch, "k": k,
              "device": str(dev), "date": time.strftime("%Y-%m-%d")}
    # Warm the H2D path first, so neither arm's upload column carries a
    # cold first put. GC between arms so host RSS from arm 1 can't distort
    # arm 2.
    import gc

    warm = jax.device_put(emb[:65536])
    while not warm.is_ready():
        time.sleep(0.01)
    del warm

    # PHASE 1 — time BOTH installs back to back, in the same process
    # state, before the match passes.
    arms = ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))
    galleries = {}
    for dtype, name in arms:
        gc.collect()
        g = ShardedGallery(capacity=rows, dim=dim, mesh=make_mesh(),
                           store_dtype=dtype)
        g.add(emb, lab)
        ok = g._await_residency(g.data, 600.0)
        t0 = time.perf_counter()
        g._install(g._host_emb, g._host_lab, g._host_val, g.size)
        ok = g._await_residency(g.data, 600.0) and ok
        upload_s = time.perf_counter() - t0
        result[name] = {
            "upload_s": round(upload_s, 2), "residency_ok": bool(ok),
            "gallery_bytes": int(rows * dim * jnp.dtype(dtype).itemsize),
        }
        _log(f"[{name}] install (pre-readback) {upload_s:.2f}s")
        galleries[name] = g

    # int8 arm (still phase 1 — upload before any readback): the IVF
    # quantizer's storage format, per-row scale + int8 rows.
    from opencv_facerecognizer_tpu.parallel.quantizer import quantize_rows

    gc.collect()
    q8_host, scale_host = quantize_rows(emb)
    t0 = time.perf_counter()
    q8_dev = jax.device_put(q8_host)
    scale_dev = jax.device_put(scale_host)
    int8_ok = True
    deadline = time.monotonic() + 600.0
    while time.monotonic() < deadline:
        try:
            if q8_dev.is_ready() and scale_dev.is_ready():
                break
        except (AttributeError, NotImplementedError):
            break
        time.sleep(0.01)
    else:
        int8_ok = False
    result["int8"] = {
        "upload_s": round(time.perf_counter() - t0, 2),
        "residency_ok": int8_ok,
        "gallery_bytes": int(rows * dim + rows * 4),  # q8 + f32 scales
    }
    _log(f"[int8] install (pre-readback) {result['int8']['upload_s']:.2f}s")

    # PHASE 2 — chained match timing (readbacks allowed from here on).
    q_dev = jnp.asarray(q)
    for dtype, name in arms:
        g = galleries[name]
        match = g._matcher(k, g.data)

        def chain(n):
            labels, vals, idx = match(q_dev, g.data.embeddings,
                                      g.data.valid, g.data.labels)
            for _ in range(n - 1):
                q2 = q_dev + vals[0, 0] * 1e-30  # device-side dependency
                labels, vals, idx = match(q2, g.data.embeddings,
                                          g.data.valid, g.data.labels)
            return np.asarray(vals).sum()

        chain(2)  # compile + warm
        k1, k2 = 4, 64
        t1s, t2s = [], []
        for _ in range(3):
            t0 = time.perf_counter(); chain(k1); t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter(); chain(k2); t2s.append(time.perf_counter() - t0)
        ms = (min(t2s) - min(t1s)) / (k2 - k1) * 1e3
        result[name]["match_ms_per_call"] = round(ms, 3)
        _log(f"[{name}] match {ms:.3f} ms/call")
        if name == "f32":
            # Reference top-1 for the int8 accuracy column below.
            f32_vals, f32_idx = (np.asarray(v) for v in
                                 match(q_dev, g.data.embeddings,
                                       g.data.valid, g.data.labels)[1:])
        del galleries[name], g

    # int8 match arm: dequantize in-graph (bf16) then the SAME exact
    # streaming kernel — the IVF stage-2 cost model at full-gallery scale.
    from opencv_facerecognizer_tpu.ops.ivf_match import tie_aware_agreement
    from opencv_facerecognizer_tpu.ops.pallas_match import streaming_match_topk

    valid_dev = jnp.ones((rows,), bool)
    interpret = jax.devices()[0].platform != "tpu"

    @jax.jit
    def int8_match(q, q8d, sd, valid):
        gal = q8d.astype(jnp.bfloat16) * sd.astype(jnp.bfloat16)[:, None]
        return streaming_match_topk(q, gal, valid, k=k, interpret=interpret)

    def chain8(n):
        vals, idx = int8_match(q_dev, q8_dev, scale_dev, valid_dev)
        for _ in range(n - 1):
            vals, idx = int8_match(q_dev + vals[0, 0] * 1e-30, q8_dev,
                                   scale_dev, valid_dev)
        return np.asarray(vals).sum()

    chain8(2)
    k1, k2 = 4, 64
    t1s, t2s = [], []
    for _ in range(3):
        t0 = time.perf_counter(); chain8(k1); t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); chain8(k2); t2s.append(time.perf_counter() - t0)
    ms = (min(t2s) - min(t1s)) / (k2 - k1) * 1e3
    result["int8"]["match_ms_per_call"] = round(ms, 3)
    i8_vals, i8_idx = (np.asarray(v) for v in
                       int8_match(q_dev, q8_dev, scale_dev, valid_dev))
    result["int8"]["tie_aware_top1_agreement_vs_f32"] = round(
        tie_aware_agreement(i8_vals, i8_idx, f32_vals, f32_idx), 4)
    result["int8"]["max_abs_sim_diff_vs_f32"] = round(
        float(np.max(np.abs(i8_vals.reshape(-1) - f32_vals.reshape(-1)))), 6)
    _log(f"[int8] match {ms:.3f} ms/call, top-1 agreement "
         f"{result['int8']['tie_aware_top1_agreement_vs_f32']}")

    f, b = result["f32"], result["bf16"]
    result["upload_speedup"] = round(f["upload_s"] / b["upload_s"], 2)
    result["match_speedup"] = round(
        f["match_ms_per_call"] / b["match_ms_per_call"], 2)
    result["int8_match_speedup_vs_f32"] = round(
        f["match_ms_per_call"] / result["int8"]["match_ms_per_call"], 2)
    path = os.path.join(REPO, "BENCH_DETAIL.json")
    try:
        detail = json.load(open(path))
    except (OSError, json.JSONDecodeError):
        detail = {}
    detail["gallery_dtype"] = result
    from opencv_facerecognizer_tpu.utils.serialization import atomic_write_json

    atomic_write_json(path, detail)
    _log("merged gallery_dtype into BENCH_DETAIL.json")
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()

"""The table of peaks and the functions that count a kernel's work.

Copied from ``bench.py``'s ``DEVICE_PEAKS`` so that a later PR to the
program cannot move the yardstick. Source: Google Cloud documentation,
"TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip). A device kind that is
not listed is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, Tuple

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in DEVICE_PEAKS:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[device_kind]


def streaming_match_topk(q: int, n: int, d: int) -> Tuple[float, float]:
    """(operations, bytes) the top-k match of ``q`` queries over ``n``
    gallery rows of width ``d`` needs: one multiply-add per query, row and
    component; the bf16 gallery read once, the f32 queries read once. The
    top-k merge and the [q, k] outputs are left out (k is 1)."""
    return 2.0 * q * n * d, n * d * 2.0 + q * d * 4.0


def least_seconds(ops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    compute = ops / (peaks["bf16_tflops"] * 1e12)
    memory = nbytes / (peaks["hbm_gb_per_s"] * 1e9)
    return (compute, "compute") if compute >= memory else (memory, "memory")


#: name -> function(q, n, d), found by the ``roofline`` reader from the
#: layer metric's file
COST_FUNCTIONS = {"streaming_match_topk": streaming_match_topk}

"""The chained-differencing timing instrument, shared by every benchmark
(bench.py, scripts/bench_lifecycle.py, scripts/explore_perf.py) so the
artifacts cannot silently diverge in methodology.

Why this exists: it was built for a backend whose ``block_until_ready`` did
not await execution and whose blocking readbacks quantized at ~100 ms, so
per-iteration wall timing was fiction there. K iterations are serialized
INSIDE one jit via a 1e-30-scaled data dependency and the whole chain is
timed with a single readback; the per-iteration cost is the difference of
two chain lengths' minima:

    (min T(K2) - min T(K1)) / (K2 - K1)

Jitter only ever ADDS to a single chain's wall time, so min-of-repeats per
length is taken BEFORE differencing (min-ing individual pair diffs is
biased low). K2 escalates up a ladder until the delta clears the readback
quantization.

On the locally attached chip ``block_until_ready`` DOES await
(``chip_smoke.py``'s timing basis: 16 chained 4096^3 bf16 matmuls take 1.89x
the time of 8, ~183 TFLOP/s on a TPU v5 lite), so the standard method —
host clock around work ended by ``block_until_ready`` — is valid there and
the benchmark rebuild (ROADMAP Speed 0) decides whether this instrument
stays.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

CHAIN_K1 = 4
#: Escalation ladder: the chain delta must dwarf the backend's ~100 ms
#: readback quantization; fast configs need the long chains. The top rung
#: sets the resolution floor: MIN_DELTA_S / (8192 - 4) ~= 31 us/iter —
#: below every per-stage cost this framework measures (the cheapest, the
#: detector forward at 0.199 ms/batch, needs k2 >= ~1260 to clear 0.25 s).
CHAIN_K2_LADDER = (34, 154, 1024, 8192)
MIN_DELTA_S = 0.25
MEASURE_PAIRS = 3


def measure_chained(
    run_chain: Callable[[int], float],
    *,
    k1: int = CHAIN_K1,
    k2_ladder: Sequence[int] = CHAIN_K2_LADDER,
    min_delta_s: float = MIN_DELTA_S,
    pairs: int = MEASURE_PAIRS,
) -> Tuple[list, list, int, Optional[float]]:
    """min-of-chains differencing with K2 escalation.

    ``run_chain(k)`` must execute the k-length chain end-to-end (warm
    compile included on its first call per k) and return the wall seconds
    of ONE timed run. Returns (t_k1_samples, t_k2_samples, k2_used,
    seconds_per_iteration_or_None).
    """
    t1s = [run_chain(k1) for _ in range(pairs)]
    t2s, k2, delta = [], k2_ladder[0], 0.0
    resolved = False
    for k2 in k2_ladder:
        t2s = [run_chain(k2) for _ in range(pairs)]
        delta = min(t2s) - min(t1s)
        if delta >= min_delta_s:
            resolved = True
            break
    if not resolved:
        # Ladder exhausted without the delta ever clearing the readback
        # quantization: the measurement is under-resolved, not merely fast.
        # Reporting it as a valid per-iteration time would launder ~100 ms
        # readback noise into the artifacts.
        return t1s, t2s, k2, None
    per_iter = delta / (k2 - k1)
    return t1s, t2s, k2, (per_iter if per_iter > 1e-6 else None)


def scalar_chain_ms(
    scalar_fn: Callable[..., "object"],
    args: tuple,
    **kwargs,
) -> Optional[float]:
    """ms/iteration of ``scalar_fn(*args) -> f32 scalar`` via the chained
    instrument. The LAST element of ``args`` must be the array the
    dependency threads through (iteration i sees ``args[-1] + dep``)."""
    import jax
    import jax.numpy as jnp

    def chained(k, *a):
        def body(i, carry):
            dep, acc = carry
            out = scalar_fn(*a[:-1], a[-1] + dep)
            dep = out * 1e-30
            return dep, acc + out

        return jax.lax.fori_loop(0, k, body,
                                 (jnp.float32(0.0), jnp.float32(0.0)))[1]

    jc = jax.jit(chained, static_argnums=0)

    def run_chain(k):
        _ = np.asarray(jc(k, *args))  # warm: compile this k
        t0 = time.perf_counter()
        _ = np.asarray(jc(k, *args))  # one readback forces the whole chain
        return time.perf_counter() - t0

    *_rest, per_iter = measure_chained(run_chain, **kwargs)
    return None if per_iter is None else per_iter * 1e3

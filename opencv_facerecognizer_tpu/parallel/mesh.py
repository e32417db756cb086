"""Mesh construction helpers.

One place decides how the available chips are split between the data-parallel
(``dp``) and gallery-tensor-parallel (``tp``) axes, so every jitted graph in
the framework agrees on axis names.

Multi-host: ``initialize_multihost()`` below brings up the jax distributed
runtime so ``jax.devices()`` spans every host's chips; ``make_mesh`` then
builds the global mesh unchanged (GSPMD inserts ICI collectives within a
slice and DCN collectives across slices — the comm-backend split the
reference delegated to ROS/NCCL-era transports is entirely XLA's job here,
SURVEY.md §5.8). Lay dp across hosts and tp within a slice so the gallery's
all-gather rides ICI.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DP_AXIS = "dp"
TP_AXIS = "tp"


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the jax distributed runtime when running multi-host.

    The TPU-native analog of the reference's process-level transport
    bootstrap: after this, ``jax.devices()`` lists every host's chips and
    the same ``make_mesh``/GSPMD graphs are *intended* to scale across DCN
    with no further code changes. Honesty note: this machine has one host,
    so the multi-host path is exercised only with a mocked
    ``jax.distributed`` (tests/test_parallel.py) — the DCN-scaling claim is
    the documented design, not a measured result here.
    Arguments default from the standard env vars
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``);
    passing any argument explicitly also triggers initialization (jax then
    autodetects whatever was left out, e.g. the coordinator on a TPU pod).

    Returns True when the distributed runtime was (already) initialized,
    False when neither arguments nor env vars ask for multi-host — callers
    never need to branch.
    """
    if jax.distributed.is_initialized():
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    if (coordinator_address is None and env_np is None
            and num_processes is None and process_id is None):
        return False  # nothing asked for multi-host; stay single-process
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=(
            num_processes if num_processes is not None
            else int(env_np) if env_np else None
        ),
        process_id=(
            process_id if process_id is not None
            else int(env_pid) if env_pid else None
        ),
    )
    return True


def make_mesh(
    dp: Optional[int] = None,
    tp: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dp, tp) mesh over ``devices`` (default: all local devices).

    With neither axis given, everything goes to ``tp`` — gallery sharding is
    the axis that changes peak capacity, while dp can also be served by
    larger per-chip batches. Given one axis, the other takes the remainder;
    given both, they must factor the device count exactly.

    What the default selects on a host of several TPU chips: the gallery's
    rows sharded over all of them, frames and nets replicated, and from
    65,536 rows a shard the Pallas streaming matcher on every shard with a
    collective merge (``parallel.gallery.match_pod_pallas``). That path has
    run on four v5e chips (a watchlist of 50,331,648 rows, 12,582,912 a
    chip): PERF.md sections 5 and 6 (PR 38) hold the numbers.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None and tp is None:
        dp, tp = 1, n
    elif dp is None:
        if n % tp:
            raise ValueError(f"tp={tp} does not divide device count {n}")
        dp = n // tp
    elif tp is None:
        if n % dp:
            raise ValueError(f"dp={dp} does not divide device count {n}")
        tp = n // dp
    if dp * tp != n:
        raise ValueError(f"dp*tp = {dp}*{tp} != device count {n}")
    arr = np.asarray(devices).reshape(dp, tp)
    return Mesh(arr, (DP_AXIS, TP_AXIS))

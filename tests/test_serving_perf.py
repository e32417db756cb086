"""Tier-1 serving-loop perf smoke (fast, deterministic, no hardware).

Drives ``bench_serving.run_smoke`` over the fake instant backend
(``runtime.fakes.InstantPipeline``), which emulates a backend's
~100 ms ``is_ready`` sync-poll floor on CPU. The overlapped pipeline
(readback worker + continuous batching) must sustain the offered load with
**zero drops** and keep ``ready_wait`` p50 far below that poll floor — the
regression tripwire for the event-driven readback design: if anything on
the serving path starts polling readbacks again, ready_wait snaps to the
floor and this fails. ``python bench_serving.py --smoke`` writes the same
row to BENCH_SERVING_smoke.json.
"""

import importlib.util
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_serving", os.path.join(REPO_ROOT, "bench_serving.py"))
bench_serving = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_serving)

#: the emulated sync-poll readback floor (ms) and the smoke's offered load.
POLL_FLOOR_MS = 100.0
FRAMES = 160
BATCH = 8


def test_perf_smoke_overlapped_readback_off_the_poll_floor():
    artifact = bench_serving.run_smoke(
        frames_n=FRAMES, rate_hz=200.0, batch_size=BATCH,
        sync_poll_floor_s=POLL_FLOOR_MS / 1e3, compute_s=0.002,
        write=False,
    )
    row = artifact["modes"]["overlapped"]
    # Sustained: every offered frame completed, none dropped, and the loop
    # actually pipelined whole batches (>= ceil(FRAMES / BATCH)).
    assert row["dropped_frames"] == 0
    assert row["completed_frames"] == FRAMES
    assert row["batches"] >= FRAMES // BATCH
    # The decomposition's readback term sits far below the poll floor: the
    # worker blocks on the array (event-driven) instead of polling is_ready
    # on the hot path. Generous margin (half the floor) keeps this
    # deterministic on a loaded CI host while still catching any
    # reintroduced poll (which would read >= ~100 ms).
    ready_wait_p50 = row["decomposition_ms"]["ready_wait_p50_ms"]
    assert ready_wait_p50 < POLL_FLOOR_MS / 2, row["decomposition_ms"]

"""served_fps on a recorded ledger, the verdict, the cached-run count."""

import numpy as np

from benchmark import check, window


def _ledger(rate, seconds, hole=(0.0, 0.0)):
    """Completed-frame counter sampled every 10 ms at ``rate`` frames/s,
    standing still inside ``hole``."""
    t = np.arange(0, seconds + 1e-9, 0.01)
    working = ~((t >= hole[0]) & (t < hole[1]))
    return t, np.concatenate([[0], np.cumsum(working[:-1] * rate * 0.01)])


def test_served_fps_reads_lower_by_the_hole():
    t, done = _ledger(1000.0, 10.0)
    steady = window.served_rate(done[0], done[-1], t[-1] - t[0])
    t, done = _ledger(1000.0, 10.0, hole=(4.0, 6.0))
    holed = window.served_rate(done[0], done[-1], t[-1] - t[0])
    assert abs(steady - 1000.0) < 1e-6
    assert abs(holed - 800.0) < 1e-6  # 2 s of 10: lower by a fifth


def test_verdict_needs_every_number_under_its_limit():
    ok, table = check.verdict({"a": 0.1, "b": 0.0}, {"a": 0.2, "b": 0.0})
    assert ok and table == {"a": [0.1, 0.2], "b": [0.0, 0.0]}
    assert not check.verdict({"a": 0.3, "b": 0.0}, {"a": 0.2, "b": 0.0})[0]
    assert not check.verdict({"a": 0.1, "c": 0.0}, {"a": 0.2})[0]  # no limit
    assert not check.verdict({"a": float("nan")}, {"a": 0.2})[0]


def test_longest_cached_run_is_per_stream():
    flags = np.zeros(64, np.uint8)
    flags[[1, 5, 9]] = check.EXIT_CACHED      # stream 1 of 4: three in a row
    flags[[2, 10]] = check.EXIT_CACHED        # stream 2: broken by frame 6
    assert check.longest_cached_run(flags, 4, 0, 64) == 3
    assert check.longest_cached_run(flags, 4, 6, 64) == 1

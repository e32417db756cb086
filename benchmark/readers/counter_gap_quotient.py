"""Quotient of the gap between two sums of counters over a third, times a
scale: ``scale`` x max(0, sum(``minuend``) - sum(``subtrahend``)) /
sum(``denominator``), such as 1000 x (wall seconds - CPU seconds of a
section) / frames = milliseconds a frame that the section's thread was
off the CPU.

Parameters: ``minuend``, ``subtrahend`` and ``denominator`` (lists of
counter names) and ``scale``. Counters are the program's ``Metrics``
counters as their difference over the window; one that did not move is
absent from it. Nothing is returned when the denominator is 0, or when no
counter of the subtrahend moved: a program without the CPU counters gives
no reading, not the whole wall time. A subtrahend a hair over its minuend
(two clocks, read one after the other) reads 0.
"""


def gap_quotient(minuend, subtrahend, denominator, scale):
    """The three are lists of window deltas, None for a counter that did
    not move."""
    taken = [v for v in subtrahend if v is not None]
    den = sum(v for v in denominator if v is not None)
    if not taken or den <= 0:
        return None
    gap = sum(v for v in minuend if v is not None) - sum(taken)
    return float(scale) * max(0.0, gap) / den


def read(params, ctx):
    counters = ctx["counters"]
    return gap_quotient([counters.get(name) for name in params["minuend"]],
                        [counters.get(name) for name in params["subtrahend"]],
                        [counters.get(name) for name in params["denominator"]],
                        params["scale"])
